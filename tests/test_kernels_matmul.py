"""Pallas matmul / schur_update vs jnp oracle (interpret mode shape sweep)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.matmul import matmul_pallas, schur_update_pallas

SHAPES = [
    (128, 128, 128),
    (256, 128, 128),
    (128, 384, 256),
    (256, 256, 512),
]


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_matmul_matches_oracle(m, k, n, dtype, rng):
    a = jnp.asarray(rng.standard_normal((m, k)), dtype)
    b = jnp.asarray(rng.standard_normal((k, n)), dtype)
    out = matmul_pallas(a, b, interpret=True)
    want = ref.matmul_ref(a, b)
    tol = 2e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol * 10,
    )


@pytest.mark.parametrize("m,k,n", SHAPES[:2])
def test_schur_update_matches_oracle(m, k, n, rng):
    c = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    out = schur_update_pallas(c, a, b, interpret=True)
    want = ref.schur_update_ref(c, a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-4)


def test_matmul_rejects_untiled_shapes(rng):
    a = jnp.asarray(rng.standard_normal((100, 128)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
    with pytest.raises(ValueError):
        matmul_pallas(a, b, interpret=True)


def test_block_shape_sweep(rng):
    a = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    want = np.asarray(ref.matmul_ref(a, b))
    for bm, bn, bk in [(128, 128, 128), (128, 256, 128), (256, 128, 256)]:
        out = matmul_pallas(
            a, b, block_m=bm, block_n=bn, block_k=bk, interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), want, atol=2e-4)


@pytest.mark.parametrize("m,k,n", [(192, 192, 192), (17, 200, 300)])
def test_matmul_wrapper_pads_untiled_shapes(m, k, n, rng):
    # the ops wrapper zero-pads to the kernel's tiles and slices back
    from repro.kernels import ops

    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    out = ops.matmul(a, b, backend="pallas", interpret=True)
    assert out.shape == (m, n)
    want = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-4)


def test_schur_update_wrapper_pads_untiled_shapes(rng):
    from repro.kernels import ops

    c = jnp.asarray(rng.standard_normal((160, 160)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((160, 32)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((32, 160)), jnp.float32)
    out = ops.schur_update(c, a, b, backend="pallas", interpret=True)
    want = ref.schur_update_ref(c, a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-4)
