"""Paged decode/extend attention over the block-paged KV pool.

The serving hot loop reads K/V through a page table: slot ``b``'s logical
position ``t`` lives in pool page ``pages[b, t // page_size]`` at row
``t % page_size`` (entries past the allocation point at the shared *null
page*, whose garbage rows the validity mask ``t <= index`` always hides).
This module owns both shelf implementations of that read:

* :func:`paged_attention_xla` — the scatter-then-gather formulation: a
  *rolled* ``fori_loop`` page walk (:func:`gather_kv_pages`) materialises
  a contiguous ``(B, ..., max_pages * page_size, ...)`` view per K/V leaf,
  then dense masked softmax.  Peak live bytes ~= gathered view + one page
  block per leaf (the old advanced-index gather + ``moveaxis`` kept two
  full copies of the view live).
* :func:`paged_attention_pallas` — the fused kernel: a Pallas grid walks
  the page list *inside* the kernel via a scalar-prefetch index map
  (``pages[b, j]`` picks page ``j``'s pool block), accumulating
  flash-style online softmax (running max / sum / weighted accumulator in
  VMEM scratch) across pages.  No gathered view exists at any point — the
  working set is one ``(page_size, head_dim)`` block per operand — which
  is why its ``BLOCK_RESOURCES`` hint carries *no* gather multiplier and
  the resources pass scores the fused decode program strictly below the
  gather path.

Both support decode (S=1) and ``extend`` (S>=1 chunked prefill, causal
within the chunk: row ``s`` of the chunk attends positions
``<= index + s``), GQA head layouts, and — through the
``q_rope``/``kr_pool`` operands — MLA's absorbed decode, which is
structurally GQA with one KV head whose "keys" are the latent cache
``c`` (+ a separate rope channel) and whose "values" are ``c`` itself:

    scores = (q_abs . c  +  q_rope . k_rope) * scale,  out = probs . c

The page-walk loop stays *rolled* (``fori_loop`` on the XLA side, the
grid's page axis on the Pallas side) so the traced program size is
independent of ``max_pages`` — see SNIPPETS.md on loop primitives.

Pool layouts (as produced by ``repro.models.attention.cache_metas_paged``):
GQA ``(P_total, KH, page_size, D)``; MLA latent ``(P_total, page_size, r)``
reshaped by the caller to ``(P_total, 1, page_size, r)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


# -- page-table plumbing (shared by both targets and the serve engine) ---------
# the writes run under the ``kv_write`` scope, apart from the
# ``paged_attention`` block's read: a profile can tell the two apart


def gather_kv_pages(
    pool: jax.Array, pages: jax.Array, seq_axis: int
) -> jax.Array:
    """Gather a per-slot contiguous K/V view from the page pool.

    ``pool`` (P_total, ..., page_size @ seq_axis, ...), ``pages``
    (B, max_pages) -> (B, ..., max_pages * page_size @ seq_axis, ...).

    The walk is a rolled ``fori_loop`` writing one page block per step
    into a preallocated view — the traced program holds the view plus a
    single ``(B, ..., page_size, ...)`` block, instead of the advanced-
    index gather + ``moveaxis`` pair that kept two full copies of the
    gathered view live.
    """
    b, mp = pages.shape
    ps = pool.shape[seq_axis]
    if mp == 1:  # a single page IS the view; no walk to roll
        return pool[pages[:, 0]]
    out_shape = (
        (b,) + pool.shape[1:seq_axis] + (mp * ps,) + pool.shape[seq_axis + 1 :]
    )

    def walk(j, acc):
        blk = pool[pages[:, j]]  # (B, ..., page_size @ seq_axis, ...)
        return jax.lax.dynamic_update_slice_in_dim(
            acc, blk, j * ps, axis=seq_axis
        )

    return jax.lax.fori_loop(0, mp, walk, jnp.zeros(out_shape, pool.dtype))


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


# -- the XLA target: rolled gather, then dense masked softmax ------------------


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
    b, h, s, dk = q.shape
    kh = k_pool.shape[1]
    g = h // kh
    dv = v_pool.shape[-1]
    k_view = gather_kv_pages(k_pool, pages, seq_axis=2)  # (B, KH, T, Dk)
    v_view = gather_kv_pages(v_pool, pages, seq_axis=2)
    smax = k_view.shape[2]
    qpos = index[:, None] + jnp.arange(s)  # (B, S)
    if q_rope is None:
        # division (not multiply-by-reciprocal) to stay bit-identical with
        # the contiguous decode path serving tests compare against
        qg = q.reshape(b, kh, g, s, dk).astype(jnp.float32)
        qg = qg * scale if scale is not None else qg / (dk ** 0.5)
        sc = jnp.einsum("bkgqd,bktd->bkgqt", qg, k_view.astype(jnp.float32))
    else:
        if scale is None:
            scale = 1.0 / (dk ** 0.5)
        qg = q.reshape(b, kh, g, s, dk).astype(jnp.float32)
        qr = q_rope.reshape(b, kh, g, s, -1).astype(jnp.float32)
        kr_view = gather_kv_pages(kr_pool, pages, seq_axis=2)
        sc = (
            jnp.einsum("bkgqd,bktd->bkgqt", qg, k_view.astype(jnp.float32))
            + jnp.einsum(
                "bkgqd,bktd->bkgqt", qr, kr_view.astype(jnp.float32)
            )
        ) * scale
    valid = (
        jnp.arange(smax)[None, None, None, None, :]
        <= qpos[:, None, None, :, None]
    )
    sc = jnp.where(valid, sc, _NEG)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgqt,bktd->bkgqd", p, v_view.astype(jnp.float32))
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
