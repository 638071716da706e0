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
  VMEM scratch) across pages.  Its working set is one whole page (every
  KV head) per operand, VMEM-resident.

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

Pool layout (``repro.models.attention.cache_metas_paged``): token-major
pages stacked over an attention group's layers, ``(L, P_total,
page_size, W)``.  A token's row holds every KV head side by side: GQA
``W = KH * D``, MLA's latent pool ``W = r`` and its rope pool
``W = dr``.  Every operation here takes the stacked pool and a layer
index: the decode program's layer loop carries the group's pool, and
layer ``i`` writes its new rows at ``(i, page, row)`` and reads
``pool[i, pages]`` inside the walk, so no layer's pool is ever sliced
out, and no stacked pool is written back.
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
def scatter_pages(
    pool: jax.Array,
    val: jax.Array,
    pages: jax.Array,
    index: jax.Array,
    layer: jax.Array | int,
) -> jax.Array:
    """Write each row's S new tokens into its pages of layer ``layer``.

    ``pool`` (L, P_total, page_size, W); ``val`` (B, S, W), token ``i``
    of row ``b`` landing at logical position ``index[b] + i`` (S=1 is the
    decode step, S>1 an ``extend`` chunk).  One scatter of B·S rows into
    the stacked pool: on a pool the caller carries, XLA writes it in
    place.  Rows whose table entry is the null page (freed slots, slots
    still prefilling) write into the sacrificial page.
    """
    ps = pool.shape[2]
    pos = index[:, None] + jnp.arange(val.shape[1], dtype=index.dtype)
    pid = jnp.take_along_axis(
        pages, (pos // ps).astype(jnp.int32), axis=1, mode="clip"
    )
    return pool.at[layer, pid, pos % ps].set(val.astype(pool.dtype))


@jax.named_scope("kv_write")
def insert_pages(
    pool: jax.Array, b1: jax.Array, page_ids: jax.Array, seq_axis: int
) -> jax.Array:
    """Scatter a prefilled batch-1 slot cache into the pool as whole pages.

    ``pool`` (L, P_total, page_size, W), ``b1`` a contiguous cache leaf
    (L, 1, ..., S, ...) with ``S == max_pages * page_size``; ``page_ids``
    (max_pages,) is the slot's page list, null-page entries absorbing the
    unallocated tail.  ``seq_axis`` is the leaf's per-layer sequence axis
    (batch leading), as from ``repro.models.attention.cache_seq_axes``:
    the leaf turns token-major, each token's other axes flattened into
    its row of ``W``.
    """
    layers, _, ps, width = pool.shape
    x = jnp.moveaxis(jnp.squeeze(b1, axis=1), seq_axis, 1)  # (L, S, ...)
    x = x.reshape(layers, -1, ps, width)  # (L, max_pages, ps, W)
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
    _, n_total, ps, width = k_pool.shape
    bt = block_tokens(ps, width * k_pool.dtype.itemsize, pages.shape[1])
    return bt, walk_blocks(index, pages, s, page_size=ps, block=bt,
                           null_page=n_total - 1, xp=jnp)


def paged_attention_xla(
    q: jax.Array,  # (B, H, S, Dk) — S=1 decode, S>1 extend
    k_pool: jax.Array,  # (L, P_total, page_size, KH * Dk)
    v_pool: jax.Array,  # (L, P_total, page_size, KH * Dv)
    pages: jax.Array,  # (B, max_pages) int32 page table
    index: jax.Array,  # (B,) first new-token position per slot
    layer: jax.Array | int,  # the layer of the stacked pools to read
    *,
    q_rope: jax.Array | None = None,  # MLA: (B, H, S, Dr)
    kr_pool: jax.Array | None = None,  # MLA: (L, P_total, page_size, Dr)
    scale: float | None = None,
) -> jax.Array:
    """Paged attention as a rolled walk over blocks of pages.

    The KV head count is the K pool's row width over ``Dk``.  Contract:
    each layer's last page (``P_total - 1``) is the null page,
    and every table entry past a slot's allocation names it.  Trip ``j``
    gathers table columns ``[j * ppb, (j + 1) * ppb)`` of every slot (K,
    V and MLA's rope channel), scores them in float32 and folds them into
    a running max, sum and weighted sum.  The walk makes
    ``max(walk_blocks(...))`` trips, each trip's gather of
    ``pool[layer, ids]`` its only read of the pools; a slot attends positions
    ``t <= index + s`` inside its own blocks, so an idle slot (a null-page
    row) reads nothing and returns zeros, as does every slot of a batch
    whose walk makes no trip.
    """
    b, h, s, dk = q.shape
    _, n_total, ps, width = k_pool.shape
    kh = width // dk
    g = h // kh
    dv = v_pool.shape[-1] // kh
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
        kx = pool[layer, ids].astype(jnp.float32)  # (B, ppb, ps, KH·D)
        kx = kx.reshape(b, ppb, ps, kh, -1)
        sc = jnp.einsum("bkgqd,bptkd->bkgqpt", qx, kx)
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
        vx = v_pool[layer, ids].astype(jnp.float32)
        vx = vx.reshape(b, ppb, ps, kh, dv)
        pv = jnp.einsum(
            "bkgqpt,bptkd->bkgqd", p.reshape(b, kh, g, s, ppb, ps), vx
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
    layer: jax.Array | int,
    *,
    q_rope: jax.Array | None = None,
    kr_pool: jax.Array | None = None,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused paged attention: grid (B, max_pages), page ``j``'s pool
    block of layer ``layer`` selected by the scalar-prefetched operands
    (``(layer, pages[b, j])`` in the BlockSpec index map) — the page walk
    is the grid's innermost axis, so the loop stays rolled and no
    gathered K/V view is ever materialised.  A block is a whole
    token-major page, every KV head side by side (a 64-wide head alone
    would not tile); the kernel takes each head's columns of it in turn.
    Running max/sum/accumulator live in VMEM scratch across the walk;
    masked rows (ragged lengths, the final partial page, null pages) drop
    out of both the sum and the accumulator, and pages entirely past a
    slot's newest position skip their compute.
    """
    b, h, s, dk = q.shape
    kh = k_pool.shape[-1] // dk
    dv = v_pool.shape[-1] // kh
    ps = k_pool.shape[2]
    g = h // kh
    mp = pages.shape[1]
    if scale is None:
        scale = 1.0 / (dk ** 0.5)
    r = g * s  # fused (group, chunk) rows per (b, head)
    has_rope = q_rope is not None
    dr = q_rope.shape[-1] if has_rope else 0

    def body(pages_ref, index_ref, layer_ref, q_ref, k_ref, v_ref, qr_ref,
             kr_ref, o_ref, acc_ref, m_ref, l_ref):
        del pages_ref, layer_ref  # read by the index maps
        bb = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, _NEG)
            l_ref[...] = jnp.zeros_like(l_ref)

        newest = index_ref[bb] + (s - 1)  # last valid position this chunk

        @pl.when(j * ps <= newest)  # pages fully past the slot: skip
        def _accumulate():
            for hh in range(kh):
                qb = q_ref[0, hh].astype(jnp.float32)  # (R, Dk)
                kb = k_ref[0, 0, :, hh * dk:(hh + 1) * dk]
                sc = jax.lax.dot_general(
                    qb, kb.astype(jnp.float32), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # (R, ps)
                if has_rope:
                    sc = sc + jax.lax.dot_general(
                        qr_ref[0, hh].astype(jnp.float32),
                        kr_ref[0, 0].astype(jnp.float32),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                sc = sc * scale
                # position of pool row vs. the row's own query position:
                # row r = g*S + s_idx queries position index + s_idx
                # (causal within the extend chunk; S=1 decode degenerates
                # to t <= index)
                t = j * ps + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
                qpos = index_ref[bb] + (
                    jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0) % s
                )
                valid = t <= qpos
                sc = jnp.where(valid, sc, _NEG)
                m_prev = m_ref[hh, :, :1]  # (R, 1)
                m_new = jnp.maximum(
                    m_prev, jnp.max(sc, axis=-1, keepdims=True)
                )
                # explicit re-mask: guards exp(_NEG - m) rounding when a
                # row has seen nothing but masked positions
                p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[hh] = l_ref[hh] * alpha + jnp.sum(
                    p, axis=-1, keepdims=True
                )
                vb = v_ref[0, 0, :, hh * dv:(hh + 1) * dv]
                acc_ref[hh] = acc_ref[hh] * alpha + jax.lax.dot_general(
                    p, vb.astype(jnp.float32), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                m_ref[hh] = jnp.broadcast_to(m_new, m_ref.shape[1:])

        @pl.when(j == mp - 1)
        def _flush():
            lv = l_ref[:, :, :1]
            lv = jnp.where(lv == 0.0, 1.0, lv)
            o_ref[0] = (acc_ref[...] / lv).astype(o_ref.dtype)

    # q rows fuse (group, chunk): row r <-> (g_idx = r // S, s_idx = r % S)
    page_block = lambda b_, j, pages_, index_, layer_: (
        layer_[0], pages_[b_, j], 0, 0)
    row_block = lambda b_, j, pages_, index_, layer_: (b_, 0, 0, 0)
    in_specs = [
        pl.BlockSpec((1, kh, r, dk), row_block),
        pl.BlockSpec((1, 1, ps, kh * dk), page_block),
        pl.BlockSpec((1, 1, ps, kh * dv), page_block),
    ]
    operands = [q.reshape(b, kh, r, dk), k_pool, v_pool]
    if has_rope:
        in_specs += [
            pl.BlockSpec((1, kh, r, dr), row_block),
            pl.BlockSpec((1, 1, ps, dr), page_block),
        ]
        operands += [q_rope.reshape(b, kh, r, dr), kr_pool]
        kernel = body
    else:

        def kernel(pages_ref, index_ref, layer_ref, q_ref, k_ref, v_ref,
                   o_ref, acc_ref, m_ref, l_ref):
            body(pages_ref, index_ref, layer_ref, q_ref, k_ref, v_ref, None,
                 None, o_ref, acc_ref, m_ref, l_ref)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, mp),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, kh, r, dv), row_block),
            scratch_shapes=[
                pltpu.VMEM((kh, r, dv), jnp.float32),
                pltpu.VMEM((kh, r, 128), jnp.float32),
                pltpu.VMEM((kh, r, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kh, r, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pages.astype(jnp.int32), index.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    return out.reshape(b, h, s, dv)
