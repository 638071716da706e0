"""Fused RMSNorm kernel.

One pass over the row: mean-of-squares, rsqrt, scale — fused so the
activation is read from HBM once (XLA emits separate reduce + mul passes at
f32 widths unless it fuses; the kernel makes the fusion structural).

Block: (rows_block, d) — the whole feature dim stays in VMEM (d <= 8192 f32
= 32 KiB/row).  A row block is either all the rows or a multiple of 8 (the
TPU sublane tile) with the rows zero-padded to a whole number of blocks, so
every row count lowers; padded rows normalise to zero and are sliced away.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "rows_block", "interpret"))
def rmsnorm_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    eps: float = 1e-6,
    rows_block: int = 8,
    interpret: bool = False,
) -> jax.Array:
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    if rows_block % 8:
        raise ValueError(f"rows_block {rows_block} is not a multiple of 8")
    rb = min(rows, rows_block)
    padded = -(-rows // rb) * rb
    x2 = jnp.pad(x.reshape(rows, d), ((0, padded - rows), (0, 0)))
    grid = (padded // rb,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rb, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, d), x.dtype),
        interpret=interpret,
    )(x2, w.reshape(1, d))
    return out[:rows].reshape(orig_shape)
