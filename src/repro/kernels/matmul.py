"""Blocked MXU matmul — the cuBLAS-analogue shelf entry.

Grid (M/bm, N/bn, K/bk) with the K dimension innermost ("arbitrary"
semantics) so the f32 accumulator tile stays resident in VMEM across the
contraction.  Block shapes default to 128x128x128: MXU-aligned (128 lanes,
8-sublane f32 tiles) and small enough that a (bm,bk)+(bk,bn)+(bm,bn) working
set (~192 KiB at f32) fits VMEM (~16 MiB) with ample double-buffering room.

The kernels take only shapes that tile; the public wrappers pad to
:func:`tile_dim` extents first.  float32 operands contract at
``Precision.HIGHEST``: the MXU's default f32 pass rounds inputs to
bfloat16, which the offload verify step (rtol 1e-3) rejects.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128


def tile_dim(d: int, block: int = TILE) -> tuple[int, int]:
    """(padded extent, block) for one axis.  An axis no longer than
    ``block`` is one whole-axis block, which is always a legal TPU block
    shape; a longer one pads to a multiple of ``block``."""
    if d <= block:
        return d, d
    return -(-d // block) * block, block


def pad_to(x: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """Zero-pad ``x`` at the high end of each axis up to ``shape``."""
    pads = [(0, t - s) for s, t in zip(x.shape, shape)]
    if not any(hi for _, hi in pads):
        return x
    return jnp.pad(x, pads)


def dot_precision(*xs: jax.Array) -> jax.lax.Precision | None:
    """HIGHEST when any operand is float32 (exact f32 contraction on the
    MXU), else the dtype's native single pass."""
    if any(x.dtype == jnp.float32 for x in xs):
        return jax.lax.Precision.HIGHEST
    return None


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int, precision):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
        precision=precision,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {k} vs {k2}")
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(
            f"shapes ({m},{k})x({k},{n}) must tile by "
            f"({block_m},{block_n},{block_k}); pad first (interface adapter "
            "handles this)"
        )
    grid = (m // block_m, n // block_n, k // block_k)
    out_dtype = jnp.promote_types(a.dtype, b.dtype)
    return pl.pallas_call(
        functools.partial(
            _matmul_kernel, k_steps=grid[2], precision=dot_precision(a, b)
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)


def _schur_kernel(c_ref, a_ref, b_ref, o_ref, acc_ref, *, k_steps: int,
                  precision):
    """o = c - a @ b (the LU trailing update), fused accumulate."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = c_ref[...].astype(jnp.float32)

    acc_ref[...] -= jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
        precision=precision,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def schur_update_pallas(
    c: jax.Array,
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fused C - A@B.  Saves one HBM round trip of C versus matmul-then-sub —
    this is why LU registers its own shelf kernel instead of reusing matmul."""
    m, k = a.shape
    _, n = b.shape
    if c.shape != (m, n):
        raise ValueError(f"c shape {c.shape} != ({m},{n})")
    if m % block_m or n % block_n or k % block_k:
        raise ValueError("shapes must tile by the block sizes; pad first")
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(
            _schur_kernel, k_steps=grid[2], precision=dot_precision(a, b)
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), c.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(c, a, b)


def matmul_padded(a: jax.Array, b: jax.Array, *,
                  interpret: bool = False) -> jax.Array:
    """a @ b for any (m, k) x (k, n): zero-pad to :func:`tile_dim` extents
    (padding along k adds zero products), run the kernel, slice back."""
    (m, k), n = a.shape, b.shape[1]
    (mp, bm), (kp, bk), (np_, bn) = map(tile_dim, (m, k, n))
    out = matmul_pallas(
        pad_to(a, (mp, kp)), pad_to(b, (kp, np_)),
        block_m=bm, block_n=bn, block_k=bk, interpret=interpret,
    )
    return out[:m, :n]


def schur_update_padded(c: jax.Array, a: jax.Array, b: jax.Array, *,
                        interpret: bool = False) -> jax.Array:
    """c - a @ b for any shapes, padded like :func:`matmul_padded`: the
    padded rows and columns of c are updated by zeros and sliced away."""
    (m, n), k = c.shape, a.shape[1]
    (mp, bm), (np_, bn), (kp, bk) = map(tile_dim, (m, n, k))
    out = schur_update_pallas(
        pad_to(c, (mp, np_)), pad_to(a, (mp, kp)), pad_to(b, (kp, np_)),
        block_m=bm, block_n=bn, block_k=bk, interpret=interpret,
    )
    return out[:m, :n]
