"""Mamba-2 SSD chunked kernel vs sequential-scan oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _inputs(rng, b=2, s=128, h=4, p=16, n=8):
    x = (rng.standard_normal((b, s, h, p)) * 0.5).astype(np.float32)
    dt = (np.abs(rng.standard_normal((b, s, h))) * 0.1).astype(np.float32)
    a = (-np.abs(rng.standard_normal(h))).astype(np.float32)
    bm = (rng.standard_normal((b, s, n)) * 0.3).astype(np.float32)
    cm = (rng.standard_normal((b, s, n)) * 0.3).astype(np.float32)
    return tuple(map(jnp.asarray, (x, dt, a, bm, cm)))


@pytest.mark.parametrize("chunk", [16, 32, 64, 128])
def test_xla_chunked_matches_sequential(chunk, rng):
    args = _inputs(rng)
    y_ref, h_ref = ref.ssd_ref(*args)
    y, h = ops.ssd_scan(*args, chunk=chunk, backend="xla")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=2e-5)


@pytest.mark.parametrize("chunk", [32, 64])
def test_pallas_chunks_match_sequential(chunk, rng):
    args = _inputs(rng)
    y_ref, h_ref = ref.ssd_ref(*args)
    y, h = ops.ssd_scan(*args, chunk=chunk, backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=2e-5)


def test_initial_state_carries(rng):
    args = _inputs(rng, s=64)
    y1, h1 = ops.ssd_scan(*args, chunk=32, backend="xla")
    # split the sequence: scan first half, feed state into second half
    x, dt, a, bm, cm = args
    y_a, h_a = ops.ssd_scan(
        x[:, :32], dt[:, :32], a, bm[:, :32], cm[:, :32], chunk=32, backend="xla"
    )
    y_b, h_b = ops.ssd_scan(
        x[:, 32:], dt[:, 32:], a, bm[:, 32:], cm[:, 32:], chunk=32,
        backend="xla", h0=h_a,
    )
    np.testing.assert_allclose(np.asarray(y_b), np.asarray(y1[:, 32:]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h_b), np.asarray(h1), atol=2e-5)


def test_nondivisible_seq_padding(rng):
    args = _inputs(rng, s=100)
    y_ref, h_ref = ref.ssd_ref(*args)
    y, h = ops.ssd_scan(*args, chunk=32, backend="xla")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=2e-5)


def test_decay_mask_is_causal(rng):
    # output at position t must not depend on inputs at positions > t
    args = _inputs(rng, b=1, s=64)
    x, dt, a, bm, cm = args
    y1, _ = ops.ssd_scan(*args, chunk=32, backend="xla")
    x2 = x.at[:, 48:].set(999.0)
    y2, _ = ops.ssd_scan(x2, dt, a, bm, cm, chunk=32, backend="xla")
    np.testing.assert_allclose(
        np.asarray(y1[:, :48]), np.asarray(y2[:, :48]), atol=1e-5
    )


@pytest.mark.parametrize("h", [5, 80])
def test_pallas_head_major_layout_any_head_count(h, rng):
    # head counts that are not multiples of 8 (mamba2-2.7b has 80 heads of
    # 64): the kernel's head-major blocks must still match the oracle
    args = _inputs(rng, b=1, s=64, h=h, p=8, n=8)
    y_ref, h_ref = ref.ssd_ref(*args)
    y, hf = ops.ssd_scan(*args, chunk=32, backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(h_ref), atol=2e-5)
