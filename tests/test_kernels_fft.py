"""Matmul-DFT FFT kernel vs numpy oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.fft import complex_matmul_pallas, dft_matrix


@pytest.mark.parametrize("n,m", [(128, 128), (256, 128), (128, 256)])
def test_fft2d_pallas_matches_numpy(n, m, rng):
    x = (rng.standard_normal((n, m)) + 1j * rng.standard_normal((n, m))).astype(
        np.complex64
    )
    out = ops.fft2d(jnp.asarray(x), backend="pallas", interpret=True)
    want = np.fft.fft2(x)
    scale = np.abs(want).max()
    assert np.abs(np.asarray(out) - want).max() / scale < 1e-5


@pytest.mark.parametrize("n", [256, 512])
def test_four_step_variant_matches(n, rng):
    x = (rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))).astype(
        np.complex64
    )
    out = ops.fft2d(
        jnp.asarray(x), backend="pallas", variant="four-step", interpret=True
    )
    want = np.fft.fft2(x)
    assert np.abs(np.asarray(out) - want).max() / np.abs(want).max() < 1e-5


def test_complex_matmul_kernel(rng):
    ar = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
    ai = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
    br = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
    bi = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
    yr, yi = complex_matmul_pallas(ar, ai, br, bi, interpret=True)
    want = (np.asarray(ar) + 1j * np.asarray(ai)) @ (
        np.asarray(br) + 1j * np.asarray(bi)
    )
    np.testing.assert_allclose(np.asarray(yr), want.real, atol=1e-3)
    np.testing.assert_allclose(np.asarray(yi), want.imag, atol=1e-3)


def test_dft_matrix_unitary_up_to_scale():
    fr, fi = dft_matrix(64)
    f = fr + 1j * fi
    prod = f @ f.conj().T
    np.testing.assert_allclose(prod, 64 * np.eye(64), atol=1e-3)


def test_fft2d_xla_backend(rng):
    x = (rng.standard_normal((64, 64)) + 1j * rng.standard_normal((64, 64))).astype(
        np.complex64
    )
    out = ops.fft2d(jnp.asarray(x), backend="xla")
    np.testing.assert_allclose(
        np.asarray(out), np.fft.fft2(x).astype(np.complex64), rtol=1e-4, atol=1e-3
    )


@pytest.mark.parametrize("n,m", [(192, 192), (192, 64), (100, 256)])
def test_fft2d_pallas_pads_untiled_shapes(n, m, rng):
    # n=192 does not tile by 128: the wrapper zero-pads the DFT operands
    x = (rng.standard_normal((n, m)) + 1j * rng.standard_normal((n, m))).astype(
        np.complex64
    )
    out = ops.fft2d(jnp.asarray(x), backend="pallas", interpret=True)
    assert out.shape == (n, m)
    want = np.fft.fft2(x)
    assert np.abs(np.asarray(out) - want).max() / np.abs(want).max() < 1e-5
