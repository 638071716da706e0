"""Fused RMSNorm kernel vs oracle, hypothesis shape sweep."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.rmsnorm import rmsnorm_pallas


@pytest.mark.parametrize("shape", [(8, 512), (2, 16, 256), (4, 8, 8, 128)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_rmsnorm_matches_oracle(shape, dtype, rng):
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    w = jnp.asarray(rng.standard_normal(shape[-1]), dtype)
    out = rmsnorm_pallas(x, w, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    tol = 1e-5 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@settings(max_examples=12, deadline=None)
@given(
    rows=st.integers(1, 16),
    d=st.sampled_from([64, 128, 256, 512]),
    eps=st.sampled_from([1e-6, 1e-5]),
)
def test_rmsnorm_property_sweep(rows, d, eps):
    rng = np.random.default_rng(rows * d)
    x = jnp.asarray(rng.standard_normal((rows, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(d), jnp.float32)
    out = rmsnorm_pallas(x, w, eps=eps, interpret=True)
    want = ref.rmsnorm_ref(x, w, eps=eps)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_rmsnorm_output_scale_invariant():
    # rmsnorm(cx) == rmsnorm(x) for c > 0 (up to eps): the defining invariant
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((4, 256)), jnp.float32)
    w = jnp.ones(256, jnp.float32)
    a = rmsnorm_pallas(x, w, interpret=True)
    b = rmsnorm_pallas(x * 1000.0, w, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


@pytest.mark.parametrize("rows,rows_block", [(17, 8), (5, 8), (33, 16)])
def test_rmsnorm_any_row_count(rows, rows_block, rng):
    # row blocks are all rows or a multiple of 8 (rows zero-padded), so
    # every row count has a legal TPU block
    x = jnp.asarray(rng.standard_normal((rows, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(256), jnp.float32)
    out = rmsnorm_pallas(x, w, rows_block=rows_block, interpret=True)
    assert out.shape == (rows, 256)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.rmsnorm_ref(x, w)), atol=1e-5
    )


def test_rmsnorm_rejects_untiled_row_block():
    x = jnp.ones((16, 128), jnp.float32)
    with pytest.raises(ValueError, match="multiple of 8"):
        rmsnorm_pallas(x, jnp.ones(128), rows_block=6, interpret=True)
