"""Plain float32 reference of the dense decoder family.

The equations are those of the program's dense block, written out here
from the configuration's shapes with nothing taken from the program:

    x_0 = E[t]                                      (embedding row)
    h = rms(x) * g1;  q, k, v = h Wq, h Wk, h Wv    (heads of head_dim)
    q, k = rope(q), rope(k)                         (rotate-half, all dims)
    a_i = softmax(q_i k_j^T / sqrt(head_dim), j <= i) v_j
          (query head n reads key/value head n // (heads / kv_heads))
    x = x + a Wo
    h = rms(x) * g2;  x = x + (silu(h Wg) * (h Wu)) Wd
    logits = (rms(x_L) * g_f) H                     (H = E^T when tied)

with rms(x) = x / sqrt(mean(x^2) + eps).  Every matmul runs at
``Precision.HIGHEST``, so a TPU computes it in float32 and not in one
bfloat16 pass.  The program departs from the published models in ways
each configuration file lists; the reference follows the program's
equations, since it checks the program's arithmetic, not the checkpoint.

``control`` names the control: the same reference computed one precision
below the one the configuration computes in (its ``correct.control``):
every matmul's operands and the residual stream, which the program keeps
in its compute dtype, are rounded to ``"int8"`` (symmetric, weights per
output channel, activations per row or per head row) below bfloat16, or
to ``"bfloat16"`` below float32.  Accumulation stays in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def layout(config: dict) -> dict:
    """Leaf name -> (shape, init) of this family's weights, stacked over
    layers; the embedding (and head) keep the padded vocabulary rows."""
    d, n = config["hidden_size"], config["num_hidden_layers"]
    h, kh, dh = (
        config["num_attention_heads"],
        config["num_key_value_heads"],
        config["head_dim"],
    )
    f = config["intermediate_size"]
    vp = -(-config["vocab_size"] // 256) * 256
    out = {
        "embed": ((vp, d), "normal"),
        "ln1": ((n, d), "norm"),
        "wq": ((n, d, h * dh), "normal"),
        "wk": ((n, d, kh * dh), "normal"),
        "wv": ((n, d, kh * dh), "normal"),
        "wo": ((n, h * dh, d), "normal"),
        "ln2": ((n, d), "norm"),
        "w_gate": ((n, d, f), "normal"),
        "w_up": ((n, d, f), "normal"),
        "w_down": ((n, f, d), "normal"),
        "final_norm": ((d,), "norm"),
    }
    if not config["tie_word_embeddings"]:
        out["lm_head"] = ((d, vp), "normal")
    return out


def _lower(x, axis, control):
    """``x`` rounded to the control's precision and returned in float32:
    int8 with one symmetric scale per slice along ``axis`` (the contracted
    axis), or bfloat16; unchanged without a control."""
    if control == "int8":
        s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
        s = jnp.where(s == 0, 1.0, s)
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if control == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if control is None:
        return x
    raise ValueError(f"unknown control precision {control!r}")


def _mm(a, w, control):
    """(S, K) @ (K, N), operands rounded under the control."""
    return jnp.dot(_lower(a, -1, control), _lower(w, 0, control),
                   precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (S, heads, dh), rotate-half over all of dh, positions 0..S-1."""
    s, _, dh = x.shape
    half = dh // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, control, block=512):
    """Causal GQA attention, q (S, H, dh), k/v (S, KH, dh), computed one
    block of query rows at a time so the scores of a long sequence fit."""
    s, h, dh = q.shape
    kh = k.shape[1]
    g = h // kh
    q, k, v = _lower(q, -1, control), _lower(k, -1, control), _lower(v, 0, control)
    q = q.reshape(s, kh, g, dh) / jnp.sqrt(jnp.float32(dh))
    outs = []
    for r0 in range(0, s, block):
        qb = q[r0 : r0 + block]
        sc = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HIGHEST)
        rows = r0 + jnp.arange(qb.shape[0])
        sc = jnp.where(jnp.arange(s)[None, :] <= rows[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        p = _lower(p, -1, control)
        outs.append(jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST))
    return jnp.concatenate(outs, 0).reshape(s, h * dh)


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def hidden(w: dict, tokens, cfg: tuple, control: str | None = None):
    """Final normed hidden states (S, d) of one sequence ``tokens`` (S,).
    ``cfg`` is :func:`static` of the configuration."""
    _, _, h, kh, dh, eps, theta = cfg
    x = _lower(w["embed"][tokens], -1, control)
    s = x.shape[0]

    def layer(x, lw):
        y = _rms(x, lw["ln1"], eps)
        q = _rope(_mm(y, lw["wq"], control).reshape(s, h, dh), theta)
        k = _rope(_mm(y, lw["wk"], control).reshape(s, kh, dh), theta)
        v = _mm(y, lw["wv"], control).reshape(s, kh, dh)
        attn = _mm(_attention(q, k, v, control), lw["wo"], control)
        x = _lower(x + attn, -1, control)
        y = _rms(x, lw["ln2"], eps)
        ff = jax.nn.silu(_mm(y, lw["w_gate"], control)) * _mm(y, lw["w_up"], control)
        return _lower(x + _mm(ff, lw["w_down"], control), -1, control), None

    stacked = {k_: w[k_] for k_ in (
        "ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up", "w_down"
    )}
    x, _ = jax.lax.scan(layer, x, stacked)
    return _rms(x, w["final_norm"], eps)


def head_matrix(w: dict, config: dict):
    """(d, V) head over the real vocabulary."""
    v = config["vocab_size"]
    if config["tie_word_embeddings"]:
        return w["embed"][:v].T
    return w["lm_head"][:, :v]


@functools.partial(jax.jit, static_argnames=("control", "block"))
def logit_stats(x, head, targets, control: str | None = None, block: int = 512):
    """Per row of ``x`` (S, d): the best logit, the logit of ``targets``
    and the arg-max token, computed one block of rows at a time."""
    best, tgt, arg = [], [], []
    for r0 in range(0, x.shape[0], block):
        lg = _mm(x[r0 : r0 + block], head, control)
        best.append(lg.max(-1))
        arg.append(lg.argmax(-1))
        tgt.append(jnp.take_along_axis(lg, targets[r0 : r0 + block, None], 1)[:, 0])
    return jnp.concatenate(best), jnp.concatenate(tgt), jnp.concatenate(arg)


def static(config: dict) -> tuple:
    """The hashable shape tuple :func:`hidden` is compiled for."""
    return (
        config["hidden_size"],
        config["num_hidden_layers"],
        config["num_attention_heads"],
        config["num_key_value_heads"],
        config["head_dim"],
        float(config.get("rms_norm_eps", config.get("layer_norm_eps"))),
        float(config["rope_theta"]),
    )
