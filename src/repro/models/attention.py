"""Attention mixers: GQA (llama-style) and MLA (DeepSeek-V2), with KV caches.

Three execution paths per mixer:
  * train/prefill: full-sequence causal attention through the FunctionBlock
    registry ("attention" block: ref = naive softmax einsum, xla = chunked
    online-softmax (memory-safe at 32k+), pallas = flash kernel);
  * decode: single-token attention over the cache — einsum-based, never
    materialises repeated KV heads; MLA decodes in the *absorbed* form
    (scores and values computed directly against the compressed latent
    cache, the MLA serving trick).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import blocks

# chunked attention lives on the kernel shelf now (registered there as
# ("attention", "xla") — import-order independent); re-exported for
# backward compatibility
from repro.kernels.attention_xla import attention_chunked  # noqa: F401

# page-table plumbing shared by both paged_attention shelf targets and the
# serve engine's page insert; re-exported from the kernel layer
from repro.kernels.paged_attention import (  # noqa: F401
    insert_pages,
    scatter_pages,
)
from repro.models.layers import (
    rmsnorm,
    rope,
    tp_out_einsum,
    yarn_mscale,
)
from repro.models.params import ParamMeta
from repro.sharding.utils import constrain

_NEG = -1e30


# -- parameter metas -----------------------------------------------------------


def attn_metas(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    dt = cfg.param_dtype
    if cfg.mla:
        m = cfg.mla
        h = cfg.n_heads
        return {
            "wq": ParamMeta(
                (d, h * (m.qk_nope_head_dim + m.qk_rope_head_dim)),
                ("embed", "heads"), dt,
            ),
            "w_dkv": ParamMeta((d, m.kv_lora_rank), ("embed", None), dt),
            "kv_norm": ParamMeta((m.kv_lora_rank,), (None,), dt, init="ones"),
            "w_uk": ParamMeta(
                (m.kv_lora_rank, h * m.qk_nope_head_dim), (None, "heads"), dt
            ),
            "w_uv": ParamMeta(
                (m.kv_lora_rank, h * m.v_head_dim), (None, "heads"), dt
            ),
            "w_kr": ParamMeta((d, m.qk_rope_head_dim), ("embed", None), dt),
            "wo": ParamMeta((h * m.v_head_dim, d), ("heads", "embed"), dt),
        }
    return {
        "wq": ParamMeta((d, cfg.n_heads * cfg.d_head), ("embed", "heads"), dt),
        "wk": ParamMeta((d, cfg.n_kv_heads * cfg.d_head), ("embed", "kv_heads"), dt),
        "wv": ParamMeta((d, cfg.n_kv_heads * cfg.d_head), ("embed", "kv_heads"), dt),
        "wo": ParamMeta((cfg.n_heads * cfg.d_head, d), ("heads", "embed"), dt),
    }


def cache_metas(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    """Per-layer KV cache metas (leading layer axis added by the LM)."""
    ct = cfg.compute_dtype
    if cfg.mla:
        m = cfg.mla
        return {
            "c": ParamMeta(
                (batch, max_len, m.kv_lora_rank),
                ("act_batch", "cache_seq", None), ct, init="zeros",
            ),
            "kr": ParamMeta(
                (batch, max_len, m.qk_rope_head_dim),
                ("act_batch", "cache_seq", None), ct, init="zeros",
            ),
        }
    return {
        "k": ParamMeta(
            (batch, cfg.n_kv_heads, max_len, cfg.d_head),
            ("act_batch", "kv_heads_act", "cache_seq", None), ct, init="zeros",
        ),
        "v": ParamMeta(
            (batch, cfg.n_kv_heads, max_len, cfg.d_head),
            ("act_batch", "kv_heads_act", "cache_seq", None), ct, init="zeros",
        ),
    }


def cache_metas_paged(
    cfg: ArchConfig, n_pages_total: int, page_size: int
) -> dict:
    """Block-paged pool layout: a *shared page pool* of ``n_pages_total``
    pages (the null page included) of ``page_size`` token rows, each row
    token-major: the contiguous leaf's per-token axes flattened side by
    side (GQA ``KH * D``, MLA ``r`` and ``dr``), so a decode step's new
    token is one row of every page it writes.  Slot identity moves out of
    the storage entirely — it lives in the page table the decode program
    gathers through — so the page and row axes carry no sharding names
    (multi-device serving shards slots, not pages)."""
    out = {}
    for key, m in cache_metas(cfg, 1, 1).items():
        head = [a for a in m.axes if a not in ("act_batch", "cache_seq")]
        out[key] = ParamMeta(
            (n_pages_total, page_size, math.prod(m.shape)),
            (None, None, next((a for a in head if a), None)),
            m.dtype, m.init, m.scale,
        )
    return out


def cache_seq_axes(cfg: ArchConfig) -> dict:
    """Leaf name -> sequence-axis position in the per-layer contiguous
    cache leaf (batch leading) — the engine's page-insert uses this to
    turn a prefilled slot cache into whole token-major pages."""
    return {
        key: m.axes.index("cache_seq")
        for key, m in cache_metas(cfg, 1, 1).items()
    }


# -- decode attention over a cache ----------------------------------------------
#
# ``index`` is per-slot: shape (B,), the write position of the *first* new
# token in each batch row's cache.  Continuous-batching serving
# (``repro.serve``) staggers requests across slots, so every row decodes at
# its own position; the single-sequence case is just the vector with equal
# entries.  Decode is the S=1 case of the general cached-extension step
# (S > 1 is chunked prefill: a budget-sized prompt chunk appended against
# the cache, causal within the chunk).


def _update_slot_rows(cache: jax.Array, update: jax.Array, index: jax.Array,
                      axis: int) -> jax.Array:
    """Per-batch-row ``dynamic_update_slice`` at each row's own position.

    ``cache``/``update`` share a leading batch axis; ``axis`` is the sequence
    axis *including* the batch axis.  ``index`` is (B,) int32.
    """
    return jax.vmap(
        lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(
            c, u, i, axis=axis - 1
        )
    )(cache, update, index)


def decode_attention_gqa(
    q: jax.Array,  # (B, H, S, D) — S=1 decode, S>1 chunked-prefill extend
    k_cache: jax.Array,  # (B, KH, Smax, D)
    v_cache: jax.Array,
    index: jax.Array,  # (B,): each row's first new-token position
) -> jax.Array:
    b, h, s, d = q.shape
    _, kh, smax, _ = k_cache.shape
    g = h // kh
    qg = q.reshape(b, kh, g, s, d).astype(jnp.float32) / (d ** 0.5)
    sc = jnp.einsum("bkgqd,bktd->bkgqt", qg, k_cache.astype(jnp.float32))
    qpos = index[:, None] + jnp.arange(s)  # (B, S)
    valid = (
        jnp.arange(smax)[None, None, None, None, :]
        <= qpos[:, None, None, :, None]
    )
    sc = jnp.where(valid, sc, _NEG)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgqt,bktd->bkgqd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, h, s, d).astype(q.dtype)


# -- the GQA mixer ----------------------------------------------------------------


def gqa_forward(
    p: dict,
    x: jax.Array,  # (B, S, D)
    cfg: ArchConfig,
    positions: jax.Array,  # (B, S)
    cache: dict | None = None,
    index: jax.Array | None = None,
    mode: str = "train",
    pages: jax.Array | None = None,
    layer: jax.Array | None = None,
):
    """``pages`` given (paged decode/extend): ``cache`` is the attention
    group's stacked pools and ``layer`` this layer's index in them; the
    new rows are written into the pools in place and the new pools
    returned."""
    b, s, d = x.shape
    cd = jnp.dtype(cfg.compute_dtype)
    xc = x.astype(cd)
    h, kh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = jnp.einsum("bsd,dq->bsq", xc, p["wq"].astype(cd)).reshape(b, s, h, dh)
    k = jnp.einsum("bsd,dq->bsq", xc, p["wk"].astype(cd)).reshape(b, s, kh, dh)
    v = jnp.einsum("bsd,dq->bsq", xc, p["wv"].astype(cd)).reshape(b, s, kh, dh)
    q = rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
    k = rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    q = constrain(q, "act_batch", None, "heads_act", None)
    k = constrain(k, "act_batch", None, "kv_heads_act", None)

    qt = jnp.swapaxes(q, 1, 2)  # (B,H,S,dh)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    if mode in ("decode", "extend"):
        assert cache is not None and index is not None
        if pages is not None:
            # one token-major row per new token (S > 1: an extend chunk),
            # its KV heads side by side
            k_cache = scatter_pages(
                cache["k"], k.reshape(b, s, kh * dh), pages, index, layer
            )
            v_cache = scatter_pages(
                cache["v"], v.reshape(b, s, kh * dh), pages, index, layer
            )
            # the attention read is a planner-searchable function block:
            # xla = a walk over the live page blocks with an online
            # softmax, pallas = the fused page-walk kernel
            o = blocks.call(
                "paged_attention", qt, k_cache, v_cache, pages, index, layer
            )
            new_cache = {"k": k_cache, "v": v_cache}
        else:
            k_cache = _update_slot_rows(
                cache["k"], kt.astype(cache["k"].dtype), index, axis=2
            )
            v_cache = _update_slot_rows(
                cache["v"], vt.astype(cache["v"].dtype), index, axis=2
            )
            o = decode_attention_gqa(qt, k_cache, v_cache, index)
            new_cache = {"k": k_cache, "v": v_cache}
    else:
        o = blocks.call("attention", qt, kt, vt, causal=True)
        new_cache = None
        if cache is not None:  # prefill: persist kv
            new_cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], kt.astype(cache["k"].dtype), 0, axis=2
                ),
                "v": jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], vt.astype(cache["v"].dtype), 0, axis=2
                ),
            }
    o = jnp.swapaxes(o, 1, 2).reshape(b, s, h * dh)
    o = constrain(o, "act_batch", None, "heads_act")
    out = tp_out_einsum("bsq,qd->bsd", o.astype(cd), p["wo"].astype(cd), cd)
    return out, new_cache


# -- the MLA mixer -----------------------------------------------------------------


def mla_forward(
    p: dict,
    x: jax.Array,
    cfg: ArchConfig,
    positions: jax.Array,
    cache: dict | None = None,
    index: jax.Array | None = None,
    mode: str = "train",
    pages: jax.Array | None = None,
    layer: jax.Array | None = None,
):
    """Paged decode/extend as in ``gqa_forward``: the latent and rope
    pools are the group's stacked pools, written at ``layer``."""
    m = cfg.mla
    b, s, d = x.shape
    cd = jnp.dtype(cfg.compute_dtype)
    xc = x.astype(cd)
    h = cfg.n_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    q = jnp.einsum("bsd,dq->bsq", xc, p["wq"].astype(cd))
    q = q.reshape(b, s, h, dn + dr)
    qn, qr = q[..., :dn], q[..., dn:]
    yarn = cfg.rope_scaling
    qr = rope(qr, positions, cfg.rope_theta, yarn)

    c = jnp.einsum("bsd,dr->bsr", xc, p["w_dkv"].astype(cd))
    c = rmsnorm(p["kv_norm"], c, cfg.norm_eps).astype(cd)
    kr = jnp.einsum("bsd,dr->bsr", xc, p["w_kr"].astype(cd))
    kr = rope(kr[:, :, None, :], positions, cfg.rope_theta, yarn)  # (B,S,1,dr)
    # YaRN scales the softmax by mscale(mscale_all_dim)^2 on top of
    # 1/sqrt(dn + dr), at every position
    mscale2 = 1.0
    if yarn is not None:
        mscale2 = yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2

    if mode in ("decode", "extend"):
        assert cache is not None and index is not None
        # absorbed decode: score = q_abs . c  +  qr . kr — structurally
        # GQA with one KV head whose keys/values are the latent cache
        w_uk = p["w_uk"].astype(cd).reshape(m.kv_lora_rank, h, dn)
        q_abs = jnp.einsum("bshn,rhn->bshr", qn, w_uk)  # (B,S,H,r)
        scale = mscale2 / ((dn + dr) ** 0.5)
        if pages is not None:
            c_cache = scatter_pages(cache["c"], c, pages, index, layer)
            kr_cache = scatter_pages(
                cache["kr"], kr[:, :, 0, :], pages, index, layer
            )
            ctx = blocks.call(
                "paged_attention",
                jnp.swapaxes(q_abs, 1, 2),  # (B,H,S,r)
                c_cache,  # the latent pool: one KV head of width r
                c_cache,  # ...and it doubles as the value pool
                pages, index, layer,
                q_rope=jnp.swapaxes(qr, 1, 2),  # (B,H,S,dr)
                kr_pool=kr_cache,
                scale=scale,
            )
            ctx = jnp.swapaxes(ctx, 1, 2)  # (B,S,H,r)
        else:
            c_cache = _update_slot_rows(
                cache["c"], c.astype(cache["c"].dtype), index, axis=1
            )
            kr_cache = _update_slot_rows(
                cache["kr"], kr[:, :, 0, :].astype(cache["kr"].dtype), index,
                axis=1,
            )
            c_view, kr_view = c_cache, kr_cache
            s_nope = jnp.einsum(
                "bshr,btr->bhst", q_abs.astype(jnp.float32),
                c_view.astype(jnp.float32),
            )
            s_rope = jnp.einsum(
                "bshr,btr->bhst", qr.astype(jnp.float32),
                kr_view.astype(jnp.float32),
            )
            sc = (s_nope + s_rope) * scale  # (B,H,S,T)
            smax = c_view.shape[1]
            qpos = index[:, None] + jnp.arange(s)  # (B, S)
            valid = (
                jnp.arange(smax)[None, None, None, :]
                <= qpos[:, None, :, None]
            )
            sc = jnp.where(valid, sc, _NEG)
            pattn = jax.nn.softmax(sc, axis=-1)
            ctx = jnp.einsum(
                "bhst,btr->bshr", pattn, c_view.astype(jnp.float32)
            )  # weighted latent
        w_uv = p["w_uv"].astype(cd).reshape(m.kv_lora_rank, h, dv)
        o = jnp.einsum("bshr,rhv->bshv", ctx.astype(cd), w_uv)
        new_cache = {"c": c_cache, "kr": kr_cache}
    else:
        kn = jnp.einsum("bsr,rq->bsq", c, p["w_uk"].astype(cd))
        kn = kn.reshape(b, s, h, dn)
        v = jnp.einsum("bsr,rq->bsq", c, p["w_uv"].astype(cd))
        v = v.reshape(b, s, h, dv)
        k = jnp.concatenate([kn, jnp.broadcast_to(kr, (b, s, h, dr))], axis=-1)
        qf = jnp.concatenate([qn, qr], axis=-1)
        if mscale2 != 1.0:
            # the attention block scales by 1/sqrt(dn + dr); YaRN's factor
            # goes on q first
            qf = (qf.astype(jnp.float32) * mscale2).astype(cd)
        # pin head sharding: the broadcast of the shared rope key otherwise
        # propagates "replicated heads" into the whole attention region and
        # GSPMD all-gathers every (B,H,S,D) block — TBs/step at 128 heads
        qf = constrain(qf, "act_batch", None, "heads_act", None)
        k = constrain(k, "act_batch", None, "heads_act", None)
        v = constrain(v, "act_batch", None, "heads_act", None)
        o = blocks.call(
            "attention",
            jnp.swapaxes(qf, 1, 2),
            jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2),
            causal=True,
        )
        o = jnp.swapaxes(o, 1, 2)  # (B,S,H,dv)
        new_cache = None
        if cache is not None:
            new_cache = {
                "c": jax.lax.dynamic_update_slice_in_dim(
                    cache["c"], c.astype(cache["c"].dtype), 0, axis=1
                ),
                "kr": jax.lax.dynamic_update_slice_in_dim(
                    cache["kr"], kr[:, :, 0, :].astype(cache["kr"].dtype), 0,
                    axis=1,
                ),
            }
    o = o.reshape(b, s, h * dv)
    out = tp_out_einsum("bsq,qd->bsd", o.astype(cd), p["wo"].astype(cd), cd)
    return out, new_cache


def attention_forward(
    p, x, cfg, positions, cache=None, index=None, mode="train", pages=None,
    layer=None,
):
    if cfg.mla is not None:
        return mla_forward(
            p, x, cfg, positions, cache, index, mode, pages, layer
        )
    return gqa_forward(p, x, cfg, positions, cache, index, mode, pages, layer)
