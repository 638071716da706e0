"""Matmul-DFT — the TPU-native cuFFT analogue.

A GPU FFT (cuFFT) is butterfly-based; butterflies are strided scalar work
that wastes the MXU.  The TPU-native formulation of the paper's "replace the
FFT block with a tuned library" is to express the DFT as dense matmuls that
run on the systolic array:

    2-D FFT:  Y = F_n @ X @ F_m        (DFT matrices are symmetric)

Complex arithmetic maps to 4 real MXU matmuls per stage (re/im planes).
The kernel below is a complex blocked matmul with two f32 VMEM accumulators;
``ops.fft2d`` stacks two stages (rows then columns via transpose).

Cost: direct DFT-matmul is O(n^2) per vector vs O(n log n) for a butterfly
FFT — but it is MXU-dense.  The four-step factorisation (n = n1*n2, two
matmul stages + twiddle) recovers most of the asymptotics while staying
matmul-shaped; it is implemented in ``ops.fft2d(variant="four-step")`` and
evaluated in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.matmul import pad_to, tile_dim


def dft_matrix(n: int, sign: float = -1.0) -> tuple[np.ndarray, np.ndarray]:
    """Real/imag planes of the n-point DFT matrix F[k,j] = exp(sign*2pi i kj/n)."""
    k = np.arange(n)
    angles = sign * 2.0 * np.pi * np.outer(k, k) / n
    return np.cos(angles).astype(np.float32), np.sin(angles).astype(np.float32)


def _cmm_kernel(ar_ref, ai_ref, br_ref, bi_ref, or_ref, oi_ref,
                accr_ref, acci_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        accr_ref[...] = jnp.zeros_like(accr_ref)
        acci_ref[...] = jnp.zeros_like(acci_ref)

    dot = functools.partial(
        jnp.dot, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,  # exact f32 DFT (see matmul)
    )
    ar = ar_ref[...]
    ai = ai_ref[...]
    br = br_ref[...]
    bi = bi_ref[...]
    accr_ref[...] += dot(ar, br) - dot(ai, bi)
    acci_ref[...] += dot(ar, bi) + dot(ai, br)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        or_ref[...] = accr_ref[...].astype(or_ref.dtype)
        oi_ref[...] = acci_ref[...].astype(oi_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def complex_matmul_pallas(
    ar: jax.Array,
    ai: jax.Array,
    br: jax.Array,
    bi: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(ar+i*ai) @ (br+i*bi) as 4 real MXU matmuls, tiled like matmul."""
    m, k = ar.shape
    _, n = br.shape
    if m % block_m or n % block_n or k % block_k:
        raise ValueError("shapes must tile by block sizes; pad first")
    grid = (m // block_m, n // block_n, k // block_k)
    in_spec_a = pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk))
    in_spec_b = pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j))
    out_spec = pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j))
    return pl.pallas_call(
        functools.partial(_cmm_kernel, k_steps=grid[2]),
        grid=grid,
        in_specs=[in_spec_a, in_spec_a, in_spec_b, in_spec_b],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((m, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((block_m, block_n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(ar, ai, br, bi)


def fft2d_pallas(x: jax.Array, *, interpret: bool = False,
                 block: int = 128) -> jax.Array:
    """2-D FFT of a complex array via two DFT matmul stages.

    Any (n, m): each axis pads to its :func:`tile_dim` extent, and the DFT
    operands are zero-padded along the contraction, so the padded rows and
    columns contribute nothing and the result is the top-left (n, m).
    """
    n, m = x.shape
    np_, bn = tile_dim(n, block)
    mp, bm = tile_dim(m, block)
    xr = pad_to(jnp.real(x).astype(jnp.float32), (np_, mp))
    xi = pad_to(jnp.imag(x).astype(jnp.float32), (np_, mp))

    def dft(k: int, kp: int) -> tuple[jax.Array, jax.Array]:
        fr, fi = dft_matrix(k)
        return (pad_to(jnp.asarray(fr), (kp, kp)),
                pad_to(jnp.asarray(fi), (kp, kp)))

    # rows: X @ F_m  (F symmetric)
    fr_m, fi_m = dft(m, mp)
    yr, yi = complex_matmul_pallas(
        xr, xi, fr_m, fi_m, block_m=bn, block_n=bm, block_k=bm,
        interpret=interpret,
    )
    # columns: F_n @ Y == (Y^T @ F_n)^T
    fr_n, fi_n = dft(n, np_)
    zr, zi = complex_matmul_pallas(
        yr.T, yi.T, fr_n, fi_n, block_m=bm, block_n=bn, block_k=bn,
        interpret=interpret,
    )
    return (zr.T[:n, :m] + 1j * zi.T[:n, :m]).astype(jnp.complex64)
