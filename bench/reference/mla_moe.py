"""Plain float32 reference of the latent-attention + expert family
(DeepSeek-V2), at the expert share a configuration holds.

The equations, written out from the configuration with nothing taken
from the program:

    x_0 = E[t]
    h = rms(x) * g1
    q = h Wq  -> per head [q_nope (dn) | q_rope (dr)];  q_rope = rope(q_rope)
    c = rms(h Wdkv) * g_kv                          (the latent, r wide)
    k_rope = rope(h Wkr)                            (one dr-wide head, shared)
    k_i = [c Wuk]_i | k_rope;   v_i = [c Wuv]_i     (per head i: the latent
                                                     expanded, not absorbed)
    a_i = softmax(q_i k_i^T * s, causal) v_i,  s = M^2 / sqrt(dn + dr)
    x = x + a Wo
    h = rms(x) * g2
    dense layer:  x = x + (silu(h Wg) * (h Wu)) Wd
    expert layer: p = softmax(h Wr) over every expert of the layer;
                  the top_k of p, greedy, renormalised to sum 1 only under
                  norm_topk_prob; for each of a token's top_k picks that
                  this configuration holds, add p_e * FFN_e(h) (no
                  capacity: every pick counts); the shared experts' FFN
                  (one SwiGLU of n_shared * expert width) on every token
    logits = (rms(x_L) * g_f) H

with rms(x) = x / sqrt(mean(x^2) + eps).  YaRN rope (rotate-half pairing,
as the program; a fixed permutation of the rope columns of Wq and Wkr away
from the published interleaved pairing, which seeded weights absorb):
pair i of dr/2 turns at

    f_i = theta^(-2i/dr) * m_i + theta^(-2i/dr) / factor * (1 - m_i),
    m_i = 1 - clip((i - low) / (high - low), 0, 1),
    low = floor(d(beta_fast)), high = ceil(d(beta_slow)) in [0, dr - 1],
    d(n) = dr ln(L0 / (2 pi n)) / (2 ln theta),

cos and sin are multiplied by M(mscale) / M(mscale_all_dim), and
M = 0.1 * mscale_all_dim * ln(factor) + 1.

Every weight is rounded to the configuration's parameter dtype before use
(the model is those rounded weights; ``correct.gaps`` regenerates them in
float32); every matmul runs at ``Precision.HIGHEST``.  Attention is
computed one block of query rows at a time.  ``control`` rounds the
operands of every product (and the residual stream) one precision lower,
as ``bench/reference/dense.py`` sets out.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

#: leaves of one attention block, stacked over its layers
_ATTN = ("ln1", "wq", "w_dkv", "kv_norm", "w_uk", "w_uv", "w_kr", "wo", "ln2")
#: leaves of one expert layer's router, held experts and shared experts
_EXPERTS = ("router", "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")


def _held(config: dict) -> int:
    return config["n_routed_experts"]


def _router_width(config: dict) -> int:
    return config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"]
    )


def layout(config: dict) -> dict:
    """Leaf name -> (shape, init).  Leaves of the leading dense layers
    carry a ``d_`` prefix, stacked over those layers; the expert layers'
    are stacked over the rest.  ``e_*`` are the held experts, ``s_*`` the
    shared experts as one SwiGLU."""
    d, n = config["hidden_size"], config["num_hidden_layers"]
    nd = config["first_k_dense_replace"]
    h = config["num_attention_heads"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    r = config["kv_lora_rank"]
    f, fe = config["intermediate_size"], config["moe_intermediate_size"]
    fs = config["n_shared_experts"] * fe
    e = _held(config)
    vp = -(-config["vocab_size"] // 256) * 256

    def attn(prefix, layers):
        return {
            prefix + "ln1": ((layers, d), "norm"),
            prefix + "wq": ((layers, d, h * (dn + dr)), "normal"),
            prefix + "w_dkv": ((layers, d, r), "normal"),
            prefix + "kv_norm": ((layers, r), "norm"),
            prefix + "w_uk": ((layers, r, h * dn), "normal"),
            prefix + "w_uv": ((layers, r, h * dv), "normal"),
            prefix + "w_kr": ((layers, d, dr), "normal"),
            prefix + "wo": ((layers, h * dv, d), "normal"),
            prefix + "ln2": ((layers, d), "norm"),
        }

    m = n - nd
    return {
        "embed": ((vp, d), "normal"),
        "lm_head": ((d, vp), "normal"),
        "final_norm": ((d,), "norm"),
        **attn("d_", nd),
        "d_w_gate": ((nd, d, f), "normal"),
        "d_w_up": ((nd, d, f), "normal"),
        "d_w_down": ((nd, f, d), "normal"),
        **attn("", m),
        "router": ((m, d, _router_width(config)), "normal"),
        "e_gate": ((m, e, d, fe), "normal"),
        "e_up": ((m, e, d, fe), "normal"),
        "e_down": ((m, e, fe, d), "normal"),
        "s_gate": ((m, d, fs), "normal"),
        "s_up": ((m, d, fs), "normal"),
        "s_down": ((m, fs, d), "normal"),
    }


def _lower(x, axis, control):
    """``x`` rounded to the control's precision, in float32 (int8: one
    symmetric scale per slice along ``axis``, the contracted axis)."""
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


def _swiglu(y, wg, wu, wd, control):
    return _mm(jax.nn.silu(_mm(y, wg, control)) * _mm(y, wu, control), wd,
               control)


def _yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope(x, theta, yarn):
    """x (S, heads, dr), rotate-half, positions 0..S-1, YaRN frequencies."""
    s, _, dr = x.shape
    half = dr // 2
    i = jnp.arange(half, dtype=jnp.float32)
    extra = 1.0 / theta ** (i / half)
    factor, length, fast, slow, mscale, mscale_all = yarn

    def turns_at(n):
        return dr * math.log(length / (n * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns_at(fast)), 0)
    high = min(math.ceil(turns_at(slow)), dr - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    freqs = extra * (1.0 - ramp) + extra / factor * ramp
    mag = _yarn_mscale(factor, mscale) / _yarn_mscale(factor, mscale_all)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = (jnp.cos(ang) * mag)[:, None, :], (jnp.sin(ang) * mag)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale, control, block=512):
    """Causal multi-head attention, q/k (S, H, dk), v (S, H, dv), one block
    of query rows at a time."""
    s, h, _ = q.shape
    q, k, v = _lower(q, -1, control), _lower(k, -1, control), _lower(v, 0, control)
    outs = []
    for r0 in range(0, s, block):
        qb = q[r0 : r0 + block] * scale
        sc = jnp.einsum("qhd,thd->hqt", qb, k, precision=HIGHEST)
        rows = r0 + jnp.arange(qb.shape[0])
        sc = jnp.where(jnp.arange(s)[None, :] <= rows[:, None], sc, -jnp.inf)
        p = _lower(jax.nn.softmax(sc, axis=-1), -1, control)
        outs.append(jnp.einsum("hqt,thd->qhd", p, v, precision=HIGHEST))
    return jnp.concatenate(outs, 0).reshape(s, -1)


def _to_bfloat16(x):
    """float32 ``x`` rounded to the nearest bfloat16 (ties to even), kept
    in float32, by integer arithmetic on its bits: a plain pair of
    converts would let the compiler round every layer's weights at once,
    ahead of the layer loop, and that copy does not fit beside them."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    odd = (bits >> 16) & jnp.uint32(1)
    bits = (bits + jnp.uint32(0x7FFF) + odd) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _round(lw, dtype):
    """A layer's weights as the configuration holds them."""
    if dtype == jnp.float32:
        return lw
    if dtype != jnp.bfloat16:
        raise ValueError(f"parameter dtype {dtype}: float32 or bfloat16")
    return {k: _to_bfloat16(v) for k, v in lw.items()}


def expert_layer(y, lw, cfg: tuple, control: str | None = None):
    """One expert layer's output for normed inputs y (S, d): the held
    experts' part of each token's top_k picks, and the shared experts.
    Returns (output (S, d), top_k expert ids (S, top_k))."""
    top_k, first, held, norm_topk = cfg[7:11]
    s = y.shape[0]
    gates = jax.nn.softmax(_mm(y, lw["router"], control), axis=-1)
    topv, topi = jax.lax.top_k(gates, top_k)
    if norm_topk:
        topv = topv / jnp.sum(topv, -1, keepdims=True)
    # each token's weight on each held expert: its top_k picks, every one
    # counted (no capacity); picks of experts held elsewhere fall outside
    comb = jnp.zeros((s, held), jnp.float32)
    for j in range(top_k):
        comb = comb + jax.nn.one_hot(topi[:, j] - first, held) * topv[:, j:j + 1]
    yl = _lower(y, -1, control)
    g = jnp.einsum("sd,edf->esf", yl, _lower(lw["e_gate"], 1, control),
                   precision=HIGHEST)
    u = jnp.einsum("sd,edf->esf", yl, _lower(lw["e_up"], 1, control),
                   precision=HIGHEST)
    eo = jnp.einsum("esf,efd->esd", _lower(jax.nn.silu(g) * u, -1, control),
                    _lower(lw["e_down"], 1, control), precision=HIGHEST)
    routed = jnp.einsum("se,esd->sd", comb, eo, precision=HIGHEST)
    shared = _swiglu(y, lw["s_gate"], lw["s_up"], lw["s_down"], control)
    return routed + shared, topi


def _forward(w: dict, tokens, cfg: tuple, control):
    """(final normed hidden states (S, d), top_k expert ids of every
    expert layer (layers, S, top_k))."""
    h, dn, dr, dv, eps, theta, yarn = cfg[:7]
    dt = jnp.dtype(cfg[11])
    scale = _yarn_mscale(yarn[0], yarn[5]) ** 2 / math.sqrt(dn + dr)
    x = _lower(_round({"e": w["embed"][tokens]}, dt)["e"], -1, control)
    s = x.shape[0]

    def attend(x, lw):
        y = _rms(x, lw["ln1"], eps)
        q = _mm(y, lw["wq"], control).reshape(s, h, dn + dr)
        qr = _rope(q[..., dn:], theta, yarn)
        c = _rms(_mm(y, lw["w_dkv"], control), lw["kv_norm"], eps)
        kr = _rope(_mm(y, lw["w_kr"], control)[:, None, :], theta, yarn)
        kn = _mm(c, lw["w_uk"], control).reshape(s, h, dn)
        v = _mm(c, lw["w_uv"], control).reshape(s, h, dv)
        k = jnp.concatenate([kn, jnp.broadcast_to(kr, (s, h, dr))], -1)
        q = jnp.concatenate([q[..., :dn], qr], -1)
        a = _mm(_attention(q, k, v, scale, control), lw["wo"], control)
        return _lower(x + a, -1, control)

    def dense_layer(x, lw):
        lw = _round(lw, dt)
        x = attend(x, {k_: lw["d_" + k_] for k_ in _ATTN})
        y = _rms(x, lw["d_ln2"], eps)
        ff = _swiglu(y, lw["d_w_gate"], lw["d_w_up"], lw["d_w_down"], control)
        return _lower(x + ff, -1, control), None

    def moe_layer(x, lw):
        lw = _round(lw, dt)
        x = attend(x, lw)
        ff, topi = expert_layer(_rms(x, lw["ln2"], eps), lw, cfg, control)
        return _lower(x + ff, -1, control), topi

    dense = {k_: v_ for k_, v_ in w.items() if k_.startswith("d_")}
    x, _ = jax.lax.scan(dense_layer, x, dense)
    experts = {k_: w[k_] for k_ in _ATTN + _EXPERTS}
    x, topi = jax.lax.scan(moe_layer, x, experts)
    return _rms(x, _round({"g": w["final_norm"]}, dt)["g"], eps), topi


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def hidden(w: dict, tokens, cfg: tuple, control: str | None = None):
    """Final normed hidden states (S, d) of one sequence ``tokens`` (S,).
    ``cfg`` is :func:`static` of the configuration."""
    return _forward(w, tokens, cfg, control)[0]


@functools.partial(jax.jit, static_argnames=("cfg",))
def routes(w: dict, tokens, cfg: tuple):
    """Global expert ids (expert layers, S, top_k) each position of
    ``tokens`` picks in each expert layer."""
    return _forward(w, tokens, cfg, None)[1]


@functools.partial(jax.jit, static_argnames=("vocab", "dtype"))
def _head(lm_head, vocab: int, dtype: str):
    return lm_head[:, :vocab].astype(dtype).astype(jnp.float32)


def head_matrix(w: dict, config: dict):
    """(d, V) untied head over the real vocabulary, as held."""
    return _head(w["lm_head"], config["vocab_size"], config["dtypes"]["param"])


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
    """The hashable tuple :func:`hidden` is compiled for."""
    y = config["rope_scaling"]
    if y.get("type", "yarn") != "yarn":
        raise ValueError(f"rope_scaling {y.get('type')!r}: only yarn")
    return (
        config["num_attention_heads"],
        config["qk_nope_head_dim"],
        config["qk_rope_head_dim"],
        config["v_head_dim"],
        float(config["rms_norm_eps"]),
        float(config["rope_theta"]),
        (float(y["factor"]), int(y["original_max_position_embeddings"]),
         float(y["beta_fast"]), float(y["beta_slow"]), float(y["mscale"]),
         float(y["mscale_all_dim"])),
        config["num_experts_per_tok"],
        config.get("experts_first", 0),
        _held(config),
        bool(config["norm_topk_prob"]),
        config["dtypes"]["param"],
    )
