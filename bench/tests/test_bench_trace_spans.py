"""Engine spans and block scopes read from profiler traces: the recorded
TPU traces (``small_tpu`` holds harness annotations only;
``small_tpu_engine``, three steps of a reduced paged engine with its
``serve.*`` spans and the scope map of its programs, recorded on a TPU v5
lite by ``record_engine_trace.py`` and cut to what the reductions read)
and hand-made events."""

import json
import types

import pytest

from bench import trace_reduce, trace_spans
from bench_toy import REPO

TRACES = REPO / "bench" / "tests" / "data" / "traces"
RECORDED = ["small_tpu", "small_tpu_engine"]


def _scopes(name):
    path = TRACES / f"{name}.scopes.json"
    return json.loads(path.read_text()) if path.exists() else None


@pytest.fixture(scope="module", params=RECORDED)
def recorded(request):
    path = str(TRACES / f"{request.param}.xplane.pb")
    scopes = _scopes(request.param)
    return (request.param, trace_reduce.reduce(path),
            trace_spans.reduce_spans(path, scopes), scopes, path)


def test_idle_by_span_rolls_up_to_idle_by_host(recorded):
    _, old, new, _, _ = recorded
    rolled = {}
    for label, seconds in new["idle_by_span"]:
        top = label.split("/", 1)[0]
        rolled[top] = rolled.get(top, 0.0) + seconds
    assert rolled == pytest.approx(dict(old["idle_by_host"]), rel=1e-12)
    assert {k.split("/", 1)[0] for k in rolled} <= (
        set(trace_reduce.HOST_SPANS) | {"other"}
    )


def test_scopes_partition_busy_time(recorded):
    _, old, new, _, _ = recorded
    total = 0.0
    for module, scopes in new["scopes"].items():
        assert all(s > 0 for s in scopes.values())
        # innermost-op time clipped to the window: at most the module's
        # own device time
        assert sum(scopes.values()) <= old["modules"][module]["seconds"]
        total += sum(scopes.values())
    # every busy moment belongs to exactly one innermost operation
    assert total == pytest.approx(old["busy_s"], rel=1e-9)


def test_scoped_op_names_strip_to_the_old_names(recorded):
    _, old, _, scopes, _ = recorded
    for name, _ in old["top_ops"]:
        program, scope, instr = trace_spans.scoped_op_name(
            name, scopes or {}
        ).split("/", 2)
        assert f"{program}/{instr}" == name
        assert scope == (scopes or {}).get(program, {}).get(instr, "other")


def test_op_events_carry_no_metadata():
    """Why the scope map comes from the compiled text: as JAX's reader
    gives them, the op events of a capture with ``enable_hlo_proto`` off
    hold timing alone.  (The XPlane's own event metadata carries a
    ``tf_op`` for some operations, and a ``/host:metadata`` plane the HLO
    protos; ``ProfileData`` exposes neither.)"""
    from jax.profiler import ProfileData

    path = TRACES / "small_tpu.xplane.pb"
    stats = {
        k for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/device:TPU:")
        for line in plane.lines if line.name == "XLA Ops"
        for e in line.events for k, _ in e.stats
    }
    assert stats == {
        "device_offset_ps", "device_duration_ps", "Time Scale Multiplier"
    }


def test_engine_trace_names_idle_and_blocks():
    path = TRACES / "small_tpu_engine.xplane.pb"
    r = trace_spans.reduce_spans(str(path), _scopes("small_tpu_engine"))
    assert r["engine_steps"] > 0
    idle = dict(r["idle_by_span"])
    inside = sum(v for k, v in idle.items() if k.startswith("step/serve."))
    # nearly all idle time inside the harness's ``step`` is named by an
    # engine span
    assert inside >= 0.9 * sum(
        v for k, v in idle.items() if k.split("/")[0] == "step"
    )
    decode = r["scopes"]["jit_decode_fn"]
    assert decode["paged_attention"] > 0 and decode["kv_write"] > 0
    assert {"head", "mlp", "sample"} <= set(decode)


def test_harness_only_trace_has_no_engine_steps():
    r = trace_spans.reduce_spans(str(TRACES / "small_tpu.xplane.pb"))
    assert r["engine_steps"] == 0
    assert r["scopes"] == {"jit__lambda": {"other": pytest.approx(3.9776e-05)}}


# -- hand-made events ----------------------------------------------------------


def _plane(*lines):
    return types.SimpleNamespace(lines=[
        types.SimpleNamespace(name=name, events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in events
        ])
        for name, events in lines
    ])


def test_innermost_op_pieces():
    """A loop (0-100) with two children; the second holds a grandchild.
    Each moment goes to the innermost operation running."""
    plane = _plane(
        ("XLA Modules", [("jit_f(7)", 0, 100), ("jit_g(8)", 120, 10)]),
        ("XLA Ops", [
            ("%while.1 = (..) while(..)", 0, 100),
            ("%fusion.2 = f32[] fusion(..)", 10, 20),
            ("%fusion.3 = f32[] fusion(..)", 40, 40),
            ("%copy.4 = f32[] copy(..)", 50, 10),
            ("%add.5 = f32[] add(..)", 120, 10),
        ]),
    )
    pieces = {}
    for module, instr, s, e in trace_spans._innermost(plane):
        pieces[(module, instr)] = pieces.get((module, instr), 0) + e - s
    assert pieces == {
        ("jit_f", "while.1"): 10 + 10 + 20,
        ("jit_f", "fusion.2"): 20,
        ("jit_f", "fusion.3"): 30,
        ("jit_f", "copy.4"): 10,
        ("jit_g", "add.5"): 10,
    }


HARNESS = [(0, 100, "step"), (100, 110, "bookkeeping")]
ENGINE = trace_spans._tree([
    (1, 99, "serve.step"),
    (5, 20, "serve.pages"),
    (20, 40, "serve.decode"),
    (22, 30, "serve.compile"),
    (60, 90, "serve.emit"),
])


@pytest.mark.parametrize("gap,label", [
    ((6, 10), "step/serve.pages"),
    ((24, 28), "step/serve.compile"),  # the innermost of a nested pair
    ((15, 39), "step/serve.decode"),  # innermost for 11 of 24: the most
    ((15, 35), "step/serve.compile"),  # decode's own part is 7, compile 8
    ((45, 55), "step/serve.step"),  # inside the step, between its parts
    ((99.5, 99.8), "step"),  # inside ``step`` but after ``serve.step``
    ((95, 108), "bookkeeping"),  # engine spans outside it do not count
    ((200, 210), "other"),
])
def test_gap_labels(gap, label):
    starts = [s for s, _, _ in HARNESS]
    assert trace_spans._gap_label(*gap, HARNESS, starts, ENGINE) == label
    top = trace_reduce._label(*gap, HARNESS, starts)
    assert label.split("/", 1)[0] == top
