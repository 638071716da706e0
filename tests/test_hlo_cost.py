"""Loop-aware HLO cost model: trip-count multiplication, dot flops."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_cost


def _flops_of(fn, *avals):
    txt = jax.jit(fn).lower(*avals).compile().as_text()
    return hlo_cost.analyze(txt)


def test_plain_dot_flops():
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    r = _flops_of(lambda a, b: a @ b, a, b)
    assert r["flops"] == pytest.approx(2 * 64 * 128 * 32)


def test_scan_multiplies_trip_count():
    L = 8

    def f(x, ws):
        def body(x, w):
            return jnp.dot(x, w), None

        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, 64, 64), jnp.float32)
    r = _flops_of(f, x, ws)
    assert r["flops"] == pytest.approx(2 * 64**3 * L)
    # XLA's own analysis misses the loop factor — that's why this exists
    xla = jax.jit(f).lower(x, ws).compile().cost_analysis()
    assert xla["flops"] == pytest.approx(2 * 64**3, rel=1e-3)


def test_nested_scan():
    def f(x, ws):
        def outer(x, w):
            def inner(x, _):
                return jnp.dot(x, w), None

            y, _ = jax.lax.scan(inner, x, jnp.arange(3))
            return y, None

        y, _ = jax.lax.scan(outer, x, ws)
        return y

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    ws = jax.ShapeDtypeStruct((4, 32, 32), jnp.float32)
    r = _flops_of(f, x, ws)
    assert r["flops"] == pytest.approx(2 * 32**3 * 3 * 4)


def test_shape_parser():
    elems, nbytes = hlo_cost.shape_elems_bytes("f32[16,128]{1,0}")
    assert elems == 2048 and nbytes == 8192
    elems, nbytes = hlo_cost.shape_elems_bytes("(s32[], bf16[8,8]{1,0})")
    assert nbytes == 4 + 128


def test_hbm_bytes_nonzero_and_sane():
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    r = _flops_of(lambda x: x + 1.0, a)
    # read + write of 256KB within 4x slack
    assert 0.4e6 < r["hbm_bytes"] < 3e6
