"""Mamba-2 SSD (state-space duality) chunked scan kernel.

The SSD decomposition splits the selective-scan recurrence

    h_t = exp(A dt_t) h_{t-1} + dt_t B_t x_t ,   y_t = C_t h_t

into (i) an *intra-chunk* part that is pure matmul work (MXU-friendly:
G = C B^T masked by the decay kernel), (ii) a per-chunk output state, and
(iii) a cheap *inter-chunk* recurrence over chunk states.  The kernel below
computes (i)+(ii) for one (batch, head, chunk) per program — all tiles live
in VMEM: x (L,P), B/C (L,N), the (L,L) decay/score matrices.  The O(S)
inter-chunk scan runs in jnp on top (``ops.ssd_scan``).

This is the TPU-native adaptation of a GPU selective-scan: instead of a
warp-level scan primitive, reshape the work so the MXU eats the quadratic
intra-chunk part and the sequential part shrinks by a factor of L.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_chunk_kernel(
    x_ref,  # (1, 1, L, P)
    dt_ref,  # (1, 1, L, 1)
    a_ref,  # (1, 1, 1)
    b_ref,  # (1, L, N)
    c_ref,  # (1, L, N)
    y_ref,  # (1, 1, L, P)
    state_ref,  # (1, 1, 1, N, P)
    cumdecay_ref,  # (1, 1, L, 1)
    total_ref,  # (1, 1, 1, 1, 1)
    *,
    chunk: int,
):
    L = chunk
    x = x_ref[0, 0].astype(jnp.float32)  # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (L, 1)
    a = a_ref[0, 0, 0].astype(jnp.float32)  # scalar (negative)
    bm = b_ref[0].astype(jnp.float32)  # (L, N)
    cm = c_ref[0].astype(jnp.float32)  # (L, N)

    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = ii >= jj
    a_seg = a * dt  # (L, 1)
    # inclusive prefix sum as a masked row reduction (Mosaic has no cumsum)
    a_cum = jnp.sum(
        jnp.where(causal, a_seg.reshape(1, L), 0.0), axis=1, keepdims=True
    )  # (L, 1)
    a_tot = a_cum[L - 1, 0]

    # decay kernel Lambda[i,j] = exp(a_cum[i]-a_cum[j]) on i>=j
    diff = a_cum - a_cum.reshape(1, L)  # (L, L)
    lam = jnp.where(causal, jnp.exp(diff), 0.0)

    g = jnp.dot(cm, bm.T, preferred_element_type=jnp.float32)  # (L, L)
    w = g * lam * dt.reshape(1, L)  # weight includes dt_j
    y = jnp.dot(w, x, preferred_element_type=jnp.float32)  # (L, P)

    # chunk output state: sum_j exp(a_tot - a_cum_j) dt_j B_j x_j^T
    sw = dt * jnp.exp(a_tot - a_cum)  # (L, 1)
    state = jnp.dot((bm * sw).T, x, preferred_element_type=jnp.float32)  # (N,P)

    y_ref[0, 0] = y.astype(y_ref.dtype)
    state_ref[0, 0, 0] = state.astype(state_ref.dtype)
    cumdecay_ref[0, 0] = jnp.exp(a_cum).astype(cumdecay_ref.dtype)
    total_ref[0, 0, 0] = jnp.exp(a_tot).reshape(1, 1).astype(total_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunks_pallas(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H)
    a: jax.Array,  # (H,)
    bmat: jax.Array,  # (B, S, N)
    cmat: jax.Array,  # (B, S, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    """Per-chunk SSD terms.  Returns (y_intra, states, cumdecay, totals).

    The kernel sees head-major operands: x and y as (B, H, S, P), dt and
    cumdecay as (B, H, S, 1), a and totals with two trailing unit axes.
    Every block then spans its array's last two axes whole or in
    (chunk, whole) tiles, which the TPU accepts at any head count (a
    per-head block over a seq-major (B, S, H, P) array puts 1 on the
    second-minor axis).  The transposes back to seq-major run in XLA.
    """
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    if s % chunk:
        raise ValueError(f"seq {s} % chunk {chunk} != 0")
    nc = s // chunk
    grid = (b, h, nc)
    xh = jnp.transpose(x, (0, 2, 1, 3))  # (B, H, S, P)
    dth = jnp.transpose(dt, (0, 2, 1))[..., None]  # (B, H, S, 1)
    a3 = a.reshape(h, 1, 1).astype(jnp.float32)

    y, states, cumdecay, totals = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, 1), lambda b_, h_, c_: (h_, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c_: (b_, c_, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c_: (b_, c_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec(
                (1, 1, 1, n, p), lambda b_, h_, c_: (b_, c_, h_, 0, 0)
            ),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec(
                (1, 1, 1, 1, 1), lambda b_, h_, c_: (b_, c_, h_, 0, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, n, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xh, dth, a3, bmat, cmat)
    return (
        jnp.transpose(y, (0, 2, 1, 3)),
        states,
        jnp.transpose(cumdecay[..., 0], (0, 2, 1)),
        totals[..., 0, 0],
    )
