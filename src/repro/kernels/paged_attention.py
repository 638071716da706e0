"""Paged decode/extend attention over the block-paged KV pool.

The serving hot loop reads K/V through a page table: slot ``b``'s logical
position ``t`` lives in pool page ``pages[b, t // page_size]`` at row
``t % page_size``.  Entries past a slot's allocation point at the shared
*null page*, the pool's last page (``PagePool.null_page == n_pages``).
This module owns both shelf implementations of that read:

* :func:`paged_attention_xla` — a rolled walk over *blocks* of pages
  (:func:`block_tokens` tokens each).  Each trip gathers one block of
  pages per slot with a single indexed read of each pool, scores it, and
  folds it into a running max, sum and weighted sum (flash decoding, the
  algebra of the Pallas kernel).  The trip count is computed on the
  device from the kernel's own inputs (:func:`walk_blocks`): the batch's
  highest live block, so the walk covers the longest slot and not
  ``max_pages``.  Its working set is one block per slot per operand.
* :func:`paged_attention_pallas` — the fused kernel: a Pallas grid walks
  the page list *inside* the kernel via a scalar-prefetch index map
  (``pages[b, j]`` picks page ``j``'s pool block), accumulating
  flash-style online softmax (running max / sum / weighted accumulator in
  VMEM scratch) across pages.  Its working set is one
  ``(page_size, head_dim)`` block per operand, VMEM-resident.

Both support decode (S=1) and ``extend`` (S>=1 chunked prefill, causal
within the chunk: row ``s`` of the chunk attends positions
``<= index + s``), GQA head layouts, and — through the
``q_rope``/``kr_pool`` operands — MLA's absorbed decode, which is
structurally GQA with one KV head whose "keys" are the latent cache
``c`` (+ a separate rope channel) and whose "values" are ``c`` itself:

    scores = (q_abs . c  +  q_rope . k_rope) * scale,  out = probs . c

The page walk stays *rolled* (a ``fori_loop`` with a device-computed
bound on the XLA side, the grid's page axis on the Pallas side) so the
traced program size is independent of ``max_pages`` — see SNIPPETS.md on
loop primitives.

Pool layouts (as produced by ``repro.models.attention.cache_metas_paged``):
GQA ``(P_total, KH, page_size, D)``; MLA latent ``(P_total, page_size, r)``
reshaped by the caller to ``(P_total, 1, page_size, r)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


# -- page-table plumbing (shared by both targets and the serve engine) ---------
# the writes run under the ``kv_write`` scope, apart from the
# ``paged_attention`` block's read: a profile can tell the two apart


@jax.named_scope("kv_write")
def scatter_token_pages(
    pool: jax.Array,
    val: jax.Array,
    pages: jax.Array,
    index: jax.Array,
    seq_axis: int,
) -> jax.Array:
    """Scatter each row's new token into its current page.

    ``val`` is the token slice with the sequence axis squeezed out (GQA
    (B, KH, D), MLA (B, r)); ``index`` (B,) is the logical write position.
    Rows whose table entry is the null page (freed slots, slots still
    prefilling) write into the sacrificial page.
    """
    ps = pool.shape[seq_axis]
    pid = jnp.take_along_axis(
        pages, (index[:, None] // ps).astype(jnp.int32), axis=1, mode="clip"
    )[:, 0]
    off = index % ps
    idx = (pid,) + (slice(None),) * (seq_axis - 1) + (off,)
    return pool.at[idx].set(val.astype(pool.dtype))


@jax.named_scope("kv_write")
def scatter_chunk_pages(
    pool: jax.Array,
    val: jax.Array,
    pages: jax.Array,
    index: jax.Array,
    seq_axis: int,
) -> jax.Array:
    """Scatter an S-token ``extend`` chunk into each row's page list.

    ``val`` keeps the chunk axis at ``seq_axis`` (GQA (B, KH, S, D), MLA
    (B, S, r)); token ``i`` of the chunk lands at logical position
    ``index + i``.  Rolled over the chunk so the traced program is
    independent of S.
    """
    s = val.shape[seq_axis]

    def write(i, acc):
        tok = jax.lax.dynamic_index_in_dim(
            val, i, axis=seq_axis, keepdims=False
        )
        return scatter_token_pages(acc, tok, pages, index + i, seq_axis)

    return jax.lax.fori_loop(0, s, write, pool)


@jax.named_scope("kv_write")
def insert_pages(
    pool: jax.Array, b1: jax.Array, page_ids: jax.Array, seq_axis: int
) -> jax.Array:
    """Scatter a prefilled batch-1 slot cache into the pool as whole pages.

    ``pool`` (L, P_total, ..., page_size, ...), ``b1`` (L, 1, ..., S, ...)
    with ``S == max_pages * page_size``; ``page_ids`` (max_pages,) is the
    slot's page list, null-page entries absorbing the unallocated tail.
    ``seq_axis`` positions are per-layer (batch leading), as from
    ``repro.models.attention.cache_seq_axes``.
    """
    ps = pool.shape[seq_axis + 1]
    x = jnp.squeeze(b1, axis=1)  # (L, ..., S, ...): seq back at seq_axis
    shp = x.shape
    n = shp[seq_axis] // ps
    x = x.reshape(shp[:seq_axis] + (n, ps) + shp[seq_axis + 1 :])
    x = jnp.moveaxis(x, seq_axis, 1)  # (L, max_pages, ..., ps, ...)
    return pool.at[:, page_ids].set(x.astype(pool.dtype))


# -- the XLA target: a walk over the live page blocks, online softmax ---------

#: bytes of one slot's K rows in one block of the walk: each trip gathers
#: this much K (and the matching V) per slot — 128 tokens of a 32-head,
#: 64-wide bfloat16 pool, 256 of an 8-head, 128-wide one.  Halving or
#: doubling it was slower on a TPU v5e at both of those shapes
BLOCK_BYTES = 512 << 10


def block_tokens(page_size: int, token_bytes: int, max_pages: int) -> int:
    """Tokens per block of the walk: whole pages whose K rows of one slot
    (``token_bytes`` per token, all KV heads) come to ``BLOCK_BYTES``; at
    least one page, at most ``max_pages``."""
    pages = BLOCK_BYTES // (page_size * token_bytes)
    return max(1, min(max_pages, pages)) * page_size


def walk_blocks(index, pages, s: int, *, page_size: int, block: int,
                null_page: int, xp=np):
    """Per-slot blocks the walk must visit: ``ceil((index + s) / block)``,
    capped by the blocks of the slot's allocated prefix (its last
    non-null table entry).  The walk makes ``max`` of these trips.

    One function for both sides: the kernel calls it with ``xp=jnp`` on
    its traced operands, the serve engine with ``xp=np`` on its host
    mirrors, so the engine's walk counters count the trips the device
    makes.  A row of null pages (an idle slot) needs 0 blocks however
    stale its ``index``.
    """
    col = xp.arange(1, pages.shape[1] + 1)
    allocated = xp.where(pages != null_page, col, 0).max(axis=1)
    ppb = block // page_size
    want = (index + s + block - 1) // block
    return xp.minimum(want, (allocated + ppb - 1) // ppb)


def walk_plan(k_pool: jax.Array, pages: jax.Array, index: jax.Array, s: int):
    """(tokens per block, per-slot blocks) of the walk over these kernel
    operands; the walk makes ``max`` of the blocks trips."""
    n_total, kh, ps, d = k_pool.shape
    bt = block_tokens(ps, kh * d * k_pool.dtype.itemsize, pages.shape[1])
    return bt, walk_blocks(index, pages, s, page_size=ps, block=bt,
                           null_page=n_total - 1, xp=jnp)


def paged_attention_xla(
    q: jax.Array,  # (B, H, S, Dk) — S=1 decode, S>1 extend
    k_pool: jax.Array,  # (P_total, KH, page_size, Dk)
    v_pool: jax.Array,  # (P_total, KH, page_size, Dv)
    pages: jax.Array,  # (B, max_pages) int32 page table
    index: jax.Array,  # (B,) first new-token position per slot
    *,
    q_rope: jax.Array | None = None,  # MLA: (B, H, S, Dr)
    kr_pool: jax.Array | None = None,  # MLA: (P_total, 1, page_size, Dr)
    scale: float | None = None,
) -> jax.Array:
    """Paged attention as a rolled walk over blocks of pages.

    Contract: the pool's last page (``P_total - 1``) is the null page,
    and every table entry past a slot's allocation names it.  Trip ``j``
    gathers table columns ``[j * ppb, (j + 1) * ppb)`` of every slot (K,
    V and MLA's rope channel), scores them in float32 and folds them into
    a running max, sum and weighted sum.  The walk makes
    ``max(walk_blocks(...))`` trips; a slot attends positions
    ``t <= index + s`` inside its own blocks, so an idle slot (a null-page
    row) reads nothing and returns zeros, as does every slot of a batch
    whose walk makes no trip.
    """
    b, h, s, dk = q.shape
    n_total, kh, ps, _ = k_pool.shape
    g = h // kh
    dv = v_pool.shape[-1]
    mp = pages.shape[1]
    bt, blocks = walk_plan(k_pool, pages, index, s)
    ppb = bt // ps
    # a last block that overhangs max_pages reads the null page there
    table = jnp.pad(pages, ((0, 0), (0, -(-mp // ppb) * ppb - mp)),
                    constant_values=n_total - 1)
    end = (blocks * bt)[:, None, None, None, None]  # the slot's own blocks
    qpos = (index[:, None] + jnp.arange(s))[:, None, None, :, None]
    qg = q.reshape(b, kh, g, s, dk).astype(jnp.float32)
    if q_rope is None:
        # division (not multiply-by-reciprocal), as the contiguous decode
        # path the serving tests compare against divides
        qg = qg * scale if scale is not None else qg / (dk ** 0.5)
    else:
        if scale is None:
            scale = 1.0 / (dk ** 0.5)
        qr = q_rope.reshape(b, kh, g, s, -1).astype(jnp.float32)

    def scores(qx, pool, ids):  # (B, KH, G, S, bt)
        kx = pool[ids].astype(jnp.float32)  # (B, ppb, KH, ps, D)
        sc = jnp.einsum("bkgqd,bpktd->bkgqpt", qx, kx)
        return sc.reshape(b, kh, g, s, bt)

    def trip(j, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(table, j * ppb, ppb, axis=1)
        sc = scores(qg, k_pool, ids)
        if q_rope is not None:
            sc = (sc + scores(qr, kr_pool, ids)) * scale
        t = j * bt + jnp.arange(bt)
        valid = (t <= qpos) & (t < end)
        sc = jnp.where(valid, sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        # explicit re-mask: a row that has seen only masked positions has
        # m_new == _NEG, where exp(sc - m_new) would read 1
        p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        vx = v_pool[ids].astype(jnp.float32)  # (B, ppb, KH, ps, Dv)
        pv = jnp.einsum(
            "bkgqpt,bpktd->bkgqd", p.reshape(b, kh, g, s, ppb, ps), vx
        )
        return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + pv)

    carry = (
        jnp.full((b, kh, g, s, 1), _NEG, jnp.float32),
        jnp.zeros((b, kh, g, s, 1), jnp.float32),
        jnp.zeros((b, kh, g, s, dv), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(0, jnp.max(blocks), trip, carry)
    o = acc / jnp.where(l == 0.0, 1.0, l)
    return o.reshape(b, h, s, dv).astype(q.dtype)


# -- the Pallas target: fused page walk, online softmax ------------------------


def paged_attention_pallas(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    pages: jax.Array,
    index: jax.Array,
    *,
    q_rope: jax.Array | None = None,
    kr_pool: jax.Array | None = None,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused paged attention: grid (B, KH, max_pages), page ``j``'s pool
    block selected by the scalar-prefetched table (``pages[b, j]`` in the
    BlockSpec index map) — the page walk is the grid's innermost axis, so
    the loop stays rolled and no gathered K/V view is ever materialised.
    Running max/sum/accumulator live in VMEM scratch across the walk;
    masked rows (ragged lengths, the final partial page, null pages) drop
    out of both the sum and the accumulator, and pages entirely past a
    slot's newest position skip their compute.
    """
    b, h, s, dk = q.shape
    _, kh, ps, _ = k_pool.shape
    dv = v_pool.shape[-1]
    g = h // kh
    mp = pages.shape[1]
    if scale is None:
        scale = 1.0 / (dk ** 0.5)
    r = g * s  # fused (group, chunk) rows per (b, kh) program
    has_rope = q_rope is not None

    def body(pages_ref, index_ref, q_ref, k_ref, v_ref, qr_ref, kr_ref,
             o_ref, acc_ref, m_ref, l_ref):
        bb = pl.program_id(0)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, _NEG)
            l_ref[...] = jnp.zeros_like(l_ref)

        newest = index_ref[bb] + (s - 1)  # last valid position this chunk

        @pl.when(j * ps <= newest)  # pages fully past the slot: skip
        def _accumulate():
            qb = q_ref[0, 0].astype(jnp.float32)  # (R, Dk)
            kb = k_ref[0, 0].astype(jnp.float32)  # (ps, Dk)
            sc = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (R, ps)
            if has_rope:
                sc = sc + jax.lax.dot_general(
                    qr_ref[0, 0].astype(jnp.float32),
                    kr_ref[0, 0].astype(jnp.float32),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            sc = sc * scale
            # position of pool row vs. the row's own query position:
            # row r = g*S + s_idx queries position index + s_idx (causal
            # within the extend chunk; S=1 decode degenerates to t<=index)
            t = j * ps + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            qpos = index_ref[bb] + (
                jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0) % s
            )
            valid = t <= qpos
            sc = jnp.where(valid, sc, _NEG)
            m_prev = m_ref[:, :1]  # (R, 1)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            # explicit re-mask: guards exp(_NEG - m) rounding when a row
            # has seen nothing but masked positions
            p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(
                p, axis=-1, keepdims=True
            )
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p, v_ref[0, 0].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

        @pl.when(j == mp - 1)
        def _flush():
            lv = l_ref[:, :1]
            lv = jnp.where(lv == 0.0, 1.0, lv)
            o_ref[0, 0] = (acc_ref[...] / lv).astype(o_ref.dtype)

    # q rows fuse (group, chunk): row r <-> (g_idx = r // S, s_idx = r % S)
    q_rows = q.reshape(b, kh, r, dk)
    page_block = lambda b_, k_, j, pages_, index_: (pages_[b_, j], k_, 0, 0)
    row_block = lambda b_, k_, j, pages_, index_: (b_, k_, 0, 0)
    in_specs = [
        pl.BlockSpec((1, 1, r, dk), row_block),
        pl.BlockSpec((1, 1, ps, dk), page_block),
        pl.BlockSpec((1, 1, ps, dv), page_block),
    ]
    operands = [q_rows, k_pool, v_pool]
    if has_rope:
        dr = q_rope.shape[-1]
        in_specs += [
            pl.BlockSpec((1, 1, r, dr), row_block),
            pl.BlockSpec((1, 1, ps, dr), page_block),
        ]
        operands += [q_rope.reshape(b, kh, r, dr), kr_pool]

        def kernel(pages_ref, index_ref, q_ref, k_ref, v_ref, qr_ref,
                   kr_ref, o_ref, acc_ref, m_ref, l_ref):
            body(pages_ref, index_ref, q_ref, k_ref, v_ref, qr_ref, kr_ref,
                 o_ref, acc_ref, m_ref, l_ref)
    else:

        def kernel(pages_ref, index_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref):
            body(pages_ref, index_ref, q_ref, k_ref, v_ref, None, None,
                 o_ref, acc_ref, m_ref, l_ref)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kh, mp),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, r, dv), row_block),
            scratch_shapes=[
                pltpu.VMEM((r, dv), jnp.float32),
                pltpu.VMEM((r, 128), jnp.float32),
                pltpu.VMEM((r, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kh, r, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pages.astype(jnp.int32), index.astype(jnp.int32), *operands)
    return out.reshape(b, h, s, dv)
