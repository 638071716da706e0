"""Work model of the dense decoder family: operations and least bytes
computed from a configuration's shapes, never from a compiled program, so
the yardstick reads the same work whatever implementation a later change
binds.

Counts follow the model, not the program: the head covers the real
vocabulary (not its padded width), causal attention counts each
(query, key) pair once, and weights are read once per call at the compute
dtype, the least a decode step can move.
"""

from __future__ import annotations

import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def padded_vocab(config: dict) -> int:
    return -(-config["vocab_size"] // 256) * 256


def layer_matmul_params(config: dict) -> int:
    """Weights one token meets in one layer's matmuls (q, k, v, o, and
    the gate, up and down projections)."""
    d = config["hidden_size"]
    h, kh, dh = (
        config["num_attention_heads"],
        config["num_key_value_heads"],
        config["head_dim"],
    )
    f = config["intermediate_size"]
    return 2 * d * h * dh + 2 * d * kh * dh + 3 * d * f


def param_count(config: dict) -> int:
    """Parameters as held: padded embedding (and head when untied), two
    norm gains and the matmul weights per layer, the final norm gain."""
    d, n_layers = config["hidden_size"], config["num_hidden_layers"]
    vp = padded_vocab(config)
    head = 0 if config["tie_word_embeddings"] else d * vp
    return vp * d + head + n_layers * (layer_matmul_params(config) + 2 * d) + d


def kv_bytes_per_token(config: dict) -> int:
    """Cache bytes one token position holds across all layers."""
    cb = DTYPE_BYTES[config["dtypes"]["compute"]]
    return (
        config["num_hidden_layers"] * 2 * config["num_key_value_heads"]
        * config["head_dim"] * cb
    )


def _attn_pair_flops(config: dict) -> int:
    """Operations per (query, key) pair over all layers: q·k and p·v."""
    return (
        4 * config["num_hidden_layers"] * config["num_attention_heads"]
        * config["head_dim"]
    )


def decode_flops(config: dict, contexts) -> float:
    """One decode step: each slot's token through every matmul and the
    head, attending ``contexts[b]`` positions (its own new one included)."""
    per_token = layer_matmul_params(config) * config["num_hidden_layers"]
    per_token += config["hidden_size"] * config["vocab_size"]
    return 2.0 * per_token * len(contexts) + _attn_pair_flops(config) * float(
        sum(contexts)
    )


def prefill_flops(config: dict, length: int) -> float:
    """One prefill of ``length`` real tokens (padding not counted): every
    token through the layers, causal attention over length(length+1)/2
    pairs, the head for the last position only."""
    layers = layer_matmul_params(config) * config["num_hidden_layers"]
    return (
        2.0 * layers * length
        + 2.0 * config["hidden_size"] * config["vocab_size"]
        + _attn_pair_flops(config) * length * (length + 1) / 2
    )


def decode_bytes(config: dict, contexts) -> float:
    """Least bytes of one decode step: every weight once at the compute
    dtype (the head over the real vocabulary, the embedding rows of this
    step's tokens), the cache of the ``contexts[b] - 1`` earlier positions
    read and the new position written."""
    cb = DTYPE_BYTES[config["dtypes"]["compute"]]
    d, n_layers = config["hidden_size"], config["num_hidden_layers"]
    weights = (
        layer_matmul_params(config) * n_layers
        + d * config["vocab_size"]
        + (2 * n_layers + 1) * d
    )
    kv = kv_bytes_per_token(config)
    b = len(contexts)
    return float(cb * (weights + b * d) + kv * sum(contexts))


def peaks(device_kind: str, root: pathlib.Path = HERE) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads((root / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]
