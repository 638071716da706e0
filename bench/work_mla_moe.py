"""Work model of the latent-attention + expert family (DeepSeek-V2):
operations and least bytes from a configuration's shapes, never from a
compiled program, as ``bench/work.py`` does for the dense family.

Decode counts the absorbed form the serving step computes: each head's
query is carried into the latent (``q_nope Wuk``), scored against the
``r``-wide latent and the ``dr``-wide rope key of every cached position,
and its weighted latent carried out through ``Wuv``.  Prefill counts the
expanded form (per-head keys and values from the latent).  Expert work is
the expected load of the held share: each token makes ``top_k`` picks over
the router's ``E`` outputs, so ``top_k * held / E`` of them land on held
experts, each pair one expert SwiGLU.  The head covers the real
vocabulary; causal attention counts each pair once; weights are read once
per call at the parameter dtype.
"""

from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def _shape(config: dict) -> dict:
    c = config
    return {
        "d": c["hidden_size"], "h": c["num_attention_heads"],
        "dn": c["qk_nope_head_dim"], "dr": c["qk_rope_head_dim"],
        "dv": c["v_head_dim"], "r": c["kv_lora_rank"],
        "nd": c["first_k_dense_replace"],
        "nm": c["num_hidden_layers"] - c["first_k_dense_replace"],
        "f": c["intermediate_size"], "fe": c["moe_intermediate_size"],
        "fs": c["n_shared_experts"] * c["moe_intermediate_size"],
        "k": c["num_experts_per_tok"], "held": c["n_routed_experts"],
        "E": c.get("published", {}).get("n_routed_experts",
                                         c["n_routed_experts"]),
        "L": c["num_hidden_layers"], "V": c["vocab_size"],
    }


def held_pairs_per_token(config: dict) -> float:
    """Expected (token, held expert) pairs of one token in one expert
    layer: ``top_k * held / E``."""
    s = _shape(config)
    return s["k"] * s["held"] / s["E"]


def _proj_params(s: dict) -> int:
    """Attention weights one token meets outside the latent expansion:
    q, the latent and rope-key projections, and the output."""
    return (s["d"] * s["h"] * (s["dn"] + s["dr"]) + s["d"] * (s["r"] + s["dr"])
            + s["h"] * s["dv"] * s["d"])


def _ffn_params(s: dict) -> float:
    """FFN weights one token meets, over all layers: the dense layers'
    SwiGLU; in each expert layer the router, the shared experts and the
    expected held-expert pairs."""
    expert = 3 * s["d"] * s["fe"]
    per_moe = (s["d"] * s["E"] + 3 * s["d"] * s["fs"]
               + s["k"] * s["held"] / s["E"] * expert)
    return s["nd"] * 3 * s["d"] * s["f"] + s["nm"] * per_moe


def latent_bytes_per_token(config: dict) -> int:
    """Cache bytes one position holds across all layers: its latent and
    its rope key."""
    s = _shape(config)
    return s["L"] * (s["r"] + s["dr"]) * DTYPE_BYTES[config["dtypes"]["compute"]]


def weight_bytes(config: dict) -> int:
    """Every weight once at the parameter dtype: attention, the dense
    layers, router, held and shared experts, norms, the head over the real
    vocabulary (the embedding rows a step reads are counted apart)."""
    s = _shape(config)
    attn = _proj_params(s) + 2 * s["r"] * s["h"] * s["dn"] + s["r"]
    params = (s["L"] * (attn + 2 * s["d"]) + s["nd"] * 3 * s["d"] * s["f"]
              + s["nm"] * (s["d"] * s["E"] + 3 * s["d"] * (s["fs"] + s["held"] * s["fe"]))
              + s["d"] * s["V"] + s["d"])
    return params * DTYPE_BYTES[config["dtypes"]["param"]]


def decode_flops(config: dict, contexts) -> float:
    """One decode step: each slot's token through the absorbed attention,
    the FFNs and the head, attending ``contexts[b]`` positions."""
    s = _shape(config)
    absorbed = s["h"] * s["dn"] * s["r"] + s["h"] * s["r"] * s["dv"]
    per_token = s["L"] * (_proj_params(s) + absorbed) + _ffn_params(s)
    per_token += s["d"] * s["V"]
    pair = 2 * s["L"] * s["h"] * (2 * s["r"] + s["dr"])
    return 2.0 * per_token * len(contexts) + pair * float(sum(contexts))


def decode_bytes(config: dict, contexts) -> float:
    """Least bytes of one decode step: every weight once (all held experts:
    a full batch touches each of them), the embedding rows of the step's
    tokens, the latent cache of the earlier positions read and the new one
    written."""
    b = len(contexts)
    emb = b * config["hidden_size"] * DTYPE_BYTES[config["dtypes"]["param"]]
    return float(weight_bytes(config) + emb
                 + latent_bytes_per_token(config) * sum(contexts))


def prefill_flops(config: dict, length: int) -> float:
    """One prefill of ``length`` real tokens: every token through the
    expanded attention and the FFNs, causal attention over
    length(length+1)/2 pairs, the head for the last position only."""
    s = _shape(config)
    expand = s["r"] * s["h"] * (s["dn"] + s["dv"])
    per_token = s["L"] * (_proj_params(s) + expand) + _ffn_params(s)
    pair = 2 * s["L"] * s["h"] * (s["dn"] + s["dr"] + s["dv"])
    return (2.0 * per_token * length + 2.0 * s["d"] * s["V"]
            + pair * length * (length + 1) / 2)


def moe_flops(config: dict, tokens: int) -> float:
    """The ``moe`` scope over ``tokens`` tokens, every expert layer: the
    router, the expected held-expert pairs and the shared experts."""
    s = _shape(config)
    per_token = s["d"] * s["E"] + 3 * s["d"] * s["fs"] + (
        held_pairs_per_token(config) * 3 * s["d"] * s["fe"])
    return 2.0 * s["nm"] * per_token * tokens


def moe_bytes(config: dict, tokens: int) -> float:
    """Least bytes of the ``moe`` scope over ``tokens`` tokens: router,
    held and shared expert weights once per layer, each token's input read
    and output written."""
    s = _shape(config)
    pb = DTYPE_BYTES[config["dtypes"]["param"]]
    cb = DTYPE_BYTES[config["dtypes"]["compute"]]
    weights = s["d"] * s["E"] + 3 * s["d"] * (s["fs"] + s["held"] * s["fe"])
    return float(s["nm"] * (weights * pb + 2 * tokens * s["d"] * cb))
