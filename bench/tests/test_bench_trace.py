"""The trace reduction on small traces recorded for the purpose: one on a
TPU v5e (a few steps of two jitted programs under the harness's host
annotations), one on the CPU, which has no device plane."""

import pytest

from bench import trace_reduce
from bench_toy import REPO

TRACES = REPO / "bench" / "tests" / "data" / "traces"


def test_tpu_trace_reduces():
    r = trace_reduce.reduce(str(TRACES / "small_tpu.xplane.pb"))
    assert r["devices"] == 1
    # the window is the host's ``window`` annotation
    assert r["window_s"] == pytest.approx(0.022750969)
    # busy is the union of the op intervals: under the module time (ops
    # leave gaps inside a program), far under the window
    assert r["busy_s"] == pytest.approx(3.9776e-05)
    mod = r["modules"]["jit__lambda"]
    assert mod["runs"] == 6
    assert r["busy_s"] <= mod["seconds"] < r["window_s"]
    names = [name for name, _ in r["top_ops"]]
    assert names[0] == "jit__lambda/fusion"
    assert all(" " not in n and n.startswith("jit__lambda/") for n in names)
    secs = [s for _, s in r["top_ops"]]
    assert secs == sorted(secs, reverse=True)
    # every idle moment is labelled by the host annotation it fell in
    idle = dict(r["idle_by_host"])
    assert set(idle) <= set(trace_reduce.HOST_SPANS) | {"other"}
    assert idle["bookkeeping"] > idle["arrival_sleep"] > 0
    assert sum(idle.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_trace_without_a_tpu_plane_is_an_error():
    with pytest.raises(ValueError, match="no TPU device plane"):
        trace_reduce.reduce(str(TRACES / "small_cpu.xplane.pb"))


def test_union_and_clip():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        [0, 3], [5, 8]
    ]
    assert trace_reduce._clip([(0, 3), (5, 8), (9, 10)], 2, 6) == [
        (2, 3), (5, 6)
    ]
