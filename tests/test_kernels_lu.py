"""Blocked LU vs reconstruction + scipy-style oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import matrix
from repro.kernels import ops, ref


@pytest.mark.parametrize("n", [64, 128, 200, 256])
def test_lu_reconstruction(n, rng):
    a = jnp.asarray(matrix.make_input(n, seed=n), jnp.float32)
    lu, piv = ops.lu(a, backend="xla")
    rec = ref.lu_reconstruct(lu, piv)
    np.testing.assert_allclose(np.asarray(rec), np.asarray(a), atol=5e-5)


@pytest.mark.parametrize("n", [128, 192])
def test_lu_pallas_schur_path(n, rng):
    a = jnp.asarray(matrix.make_input(n, seed=n + 1), jnp.float32)
    lu, piv = ops.lu(a, backend="pallas", interpret=True, nb=64)
    rec = ref.lu_reconstruct(lu, piv)
    np.testing.assert_allclose(np.asarray(rec), np.asarray(a), atol=5e-5)


def test_lu_matches_lapack_factorization(rng):
    # same pivoting convention as getrf => same packed LU on generic input
    a = jnp.asarray(rng.standard_normal((96, 96)), jnp.float32)
    lu_ours, piv_ours = ops.lu(a, backend="xla")
    lu_ref, piv_ref = ref.lu_ref(a)
    np.testing.assert_array_equal(np.asarray(piv_ours), np.asarray(piv_ref))
    np.testing.assert_allclose(
        np.asarray(lu_ours), np.asarray(lu_ref), rtol=2e-4, atol=2e-4
    )


def test_lu_nr_compat_interface(rng):
    a = matrix.make_input(80)
    lu, indx, d = ops.lu_nr_compat(jnp.asarray(a, jnp.float32))
    assert indx.dtype == jnp.int32
    det = float(d) * float(np.prod(np.diag(np.asarray(lu))))
    assert abs(det - np.linalg.det(a)) < 1e-2


def test_lu_identity_padding_never_pivots_into_pad(rng):
    # n=100 pads to 128; factorisation must equal the unpadded one
    a = jnp.asarray(matrix.make_input(100), jnp.float32)
    lu_p, piv_p = ops.lu(a)
    assert int(jnp.max(piv_p)) < 100
    rec = ref.lu_reconstruct(lu_p, piv_p)
    np.testing.assert_allclose(np.asarray(rec), np.asarray(a), atol=5e-5)


@pytest.mark.parametrize("n", [192, 256])
def test_lu_pallas_default_block_pads_schur(n):
    # the default nb=32 leaves trailing updates of 160, 224, ... columns:
    # the Schur kernel pads them to its tiles
    a = jnp.asarray(matrix.make_input(n, seed=n + 2), jnp.float32)
    lu_p, piv = ops.lu(a, backend="pallas", interpret=True)
    lu_x, piv_x = ops.lu(a, backend="xla")
    np.testing.assert_array_equal(np.asarray(piv), np.asarray(piv_x))
    np.testing.assert_allclose(np.asarray(lu_p), np.asarray(lu_x), atol=5e-5)
    rec = ref.lu_reconstruct(lu_p, piv)
    np.testing.assert_allclose(np.asarray(rec), np.asarray(a), atol=5e-5)
