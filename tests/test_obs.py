"""repro.obs: tracer, metrics registry, exporters, engine integration.

The observability layer's contract is threefold: recording is thread-safe
and bounded (the serve loop never blocks on its own telemetry), a
*disabled* tracer costs nothing on the hot path, and every exported view
(Chrome trace, Prometheus text, the legacy telemetry aggregates) is fed by
the same observations — parity between views is asserted, not hoped for.
"""

import json
import re
import threading
import time
import urllib.request

import pytest

from repro.core.planner import MeasurementCache
from repro.obs import (
    NULL_SPAN,
    MetricsRegistry,
    MetricsServer,
    Tracer,
    exponential_buckets,
    get_tracer,
    set_tracer,
)
from repro.obs import timeline


# -- tracer -------------------------------------------------------------------


def test_span_context_records_duration():
    tr = Tracer()
    with tr.span("work", step=3):
        time.sleep(0.002)
    (rec,) = tr.records()
    assert rec.name == "work"
    assert rec.ph == "X"
    assert rec.args == {"step": 3}
    assert rec.duration >= 0.002


def test_retroactive_span_and_instant_event():
    tr = Tracer()
    t0 = time.perf_counter()
    tr.add_span("queue", t0, t0 + 0.5, tid=7, request=1)
    tr.event("preempt", tid=7, request=1)
    spans = tr.records()
    assert [r.ph for r in spans] == ["X", "i"]
    assert spans[0].tid == 7 and spans[0].duration == pytest.approx(0.5)
    # a clock-skewed t1 < t0 clamps to zero duration instead of exporting
    # a negative dur (which trace viewers reject)
    tr.add_span("skewed", t0 + 1.0, t0 + 0.5)
    assert tr.records()[-1].duration == 0.0


def test_ring_buffer_drops_oldest_and_counts():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.event(f"e{i}")
    assert len(tr) == 4
    assert [r.name for r in tr.records()] == ["e6", "e7", "e8", "e9"]
    assert tr.dropped == 6
    assert tr.to_chrome()["otherData"]["dropped_records"] == 6
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_disabled_tracer_is_free():
    tr = Tracer(enabled=False)
    # the no-op span is one shared singleton — no allocation per call
    assert tr.span("a") is NULL_SPAN
    assert tr.span("b", tid=9, big="arg") is NULL_SPAN
    with tr.span("c"):
        pass
    tr.event("x")
    tr.add_span("y", 0.0, 1.0)
    assert len(tr) == 0


def test_default_process_tracer_disabled_and_swappable():
    assert get_tracer().enabled is False
    installed = set_tracer(Tracer())
    try:
        assert get_tracer() is installed
        with get_tracer().span("visible"):
            pass
        assert [r.name for r in installed.records()] == ["visible"]
    finally:
        set_tracer(None)
    assert get_tracer().enabled is False


def test_threaded_recording_keeps_every_span_ordered():
    """Concurrent recorders (the DeviceParallelExecutor shape): no record
    is lost, and each thread's own spans stay in its program order."""
    tr = Tracer()
    n_threads, per_thread = 8, 50
    barrier = threading.Barrier(n_threads)  # all threads alive at once,
    # so the OS can't recycle thread idents across workers

    def work(k):
        barrier.wait()
        for i in range(per_thread):
            with tr.span("job", worker=k, seq=i):
                pass

    threads = [
        threading.Thread(target=work, args=(k,)) for k in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = tr.records()
    assert len(recs) == n_threads * per_thread
    by_worker = {}
    for r in sorted(recs, key=lambda r: r.t0):
        by_worker.setdefault(r.args["worker"], []).append(r.args["seq"])
    assert set(by_worker) == set(range(n_threads))
    for seqs in by_worker.values():
        assert seqs == sorted(seqs)
    # distinct threads land on distinct tracks
    assert len({r.tid for r in recs}) == n_threads


def test_chrome_export_is_viewer_valid(tmp_path):
    tr = Tracer()
    tr.name_track(0x5E54_0001, "req 1")
    t0 = time.perf_counter()
    tr.add_span("queue", t0, t0 + 0.01, tid=0x5E54_0001, request=1)
    with tr.span("decode", batch=2):
        pass
    tr.event("complete", tid=0x5E54_0001, request=1)
    doc = tr.to_chrome()
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    # metadata names the virtual request track
    meta = [e for e in events if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"] == "req 1"
    # the exported structure passes the timeline validator and is real JSON
    path = tmp_path / "trace.json"
    tr.write_chrome(str(path))
    loaded = timeline.load_events(str(path))
    assert timeline.validate(loaded) == []
    spans = [e for e in loaded if e["ph"] == "X"]
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in spans)
    # spans sorted by start time, timestamps in µs relative to the epoch
    assert [e["ts"] for e in spans] == sorted(e["ts"] for e in spans)


def test_timeline_cli_check(tmp_path, capsys):
    tr = Tracer()
    tr.name_track(5, "req 5")
    t0 = time.perf_counter()
    tr.add_span("queue", t0, t0 + 0.01, tid=5, request=5)
    tr.add_span("prefill", t0 + 0.01, t0 + 0.03, tid=5, request=5)
    good = tmp_path / "good.json"
    tr.write_chrome(str(good))
    assert timeline.main([str(good), "--check"]) == 0
    out = capsys.readouterr().out
    assert "queue" in out and "critical path" in out

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(
        {"traceEvents": [{"ph": "X", "ts": -5, "dur": "oops"}]}
    ))
    assert timeline.main([str(bad), "--check"]) == 1


# -- metrics registry ---------------------------------------------------------


def test_counter_gauge_basics_and_kind_safety():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)  # counters only go up
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.dec(2)
    assert g.value == 5
    with pytest.raises(TypeError):
        c.set(3)  # set() is a gauge operation
    # idempotent re-register returns the same family; schema drift raises
    assert reg.counter("requests_total") is c
    with pytest.raises(ValueError):
        reg.gauge("requests_total")
    with pytest.raises(ValueError):
        reg.counter("requests_total", labelnames=("phase",))
    with pytest.raises(ValueError):
        reg.counter("bad name!")


def test_labeled_family_children_render():
    reg = MetricsRegistry()
    fam = reg.counter("phase_tokens_total", "tokens", labelnames=("phase",))
    fam.labels(phase="prefill").inc(10)
    fam.labels(phase="decode").inc(32)
    assert fam.labels(phase="decode") is fam.labels(phase="decode")
    with pytest.raises(KeyError):
        fam.labels(stage="decode")
    with pytest.raises(KeyError):
        fam.inc()  # labeled family has no sole child
    text = reg.render_prometheus()
    assert '# TYPE phase_tokens_total counter' in text
    assert 'phase_tokens_total{phase="decode"} 32' in text
    assert 'phase_tokens_total{phase="prefill"} 10' in text


def test_prometheus_escaping():
    reg = MetricsRegistry()
    reg.counter(
        "odd_total", 'help with \\ and\nnewline', labelnames=("k",)
    ).labels(k='va"l\\ue\n').inc()
    text = reg.render_prometheus()
    assert '# HELP odd_total help with \\\\ and\\nnewline' in text
    assert 'odd_total{k="va\\"l\\\\ue\\n"} 1' in text


def test_histogram_buckets_cumulative_and_sums():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.render_prometheus()
    assert 'lat_seconds_bucket{le="0.01"} 1' in text
    assert 'lat_seconds_bucket{le="0.1"} 3' in text
    assert 'lat_seconds_bucket{le="1"} 4' in text
    assert 'lat_seconds_bucket{le="+Inf"} 5' in text
    assert 'lat_seconds_count 5' in text
    sum_line = [
        line for line in text.splitlines()
        if line.startswith("lat_seconds_sum")
    ][0]
    assert float(sum_line.split()[-1]) == pytest.approx(5.605)
    with pytest.raises(ValueError):
        exponential_buckets(start=0.0)
    assert len(exponential_buckets(1e-3, 2.0, 4)) == 4


def test_registry_reset_keeps_child_handles_valid():
    reg = MetricsRegistry()
    c = reg.counter("n_total", "n", labelnames=("k",)).labels(k="a")
    h = reg.histogram("h_seconds", "h", buckets=(1.0,))
    c.inc(3)
    h.observe(0.5)
    reg.reset()
    assert c.value == 0
    assert 'h_seconds_count 0' in reg.render_prometheus()
    c.inc()  # the pre-reset handle still feeds the family
    assert 'n_total{k="a"} 1' in reg.render_prometheus()


def test_metrics_server_serves_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("up_total", "liveness").inc()
    srv = MetricsServer(reg, port=0)
    try:
        with urllib.request.urlopen(srv.url, timeout=5) as resp:
            body = resp.read().decode()
            ctype = resp.headers["Content-Type"]
        assert "up_total 1" in body
        assert "text/plain" in ctype
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                srv.url.replace("/metrics", "/other"), timeout=5
            )
    finally:
        srv.close()


# -- planner / metering integration ------------------------------------------


class _FakeSpace:
    def signature(self):
        return ("obs-test",)

    def canonical(self, cand):
        return tuple(sorted(cand))

    def build(self, cand):
        return lambda x: x * 2


def test_measurement_cache_metrics_parity():
    reg = MetricsRegistry()
    cache = MeasurementCache(metrics=reg)
    space = _FakeSpace()
    cache.measure(space, ["a"], (3,), repeats=1, warmup=0)
    cache.measure(space, ["a"], (3,), repeats=1, warmup=0)
    cache.measure(space, ["b"], (3,), repeats=1, warmup=0)
    assert (cache.hits, cache.misses) == (1, 2)
    text = reg.render_prometheus()
    assert "planner_cache_hits_total 1" in text
    assert "planner_cache_misses_total 2" in text


def test_executor_trial_spans_across_worker_threads():
    from repro.metering.executors import DeviceParallelExecutor, MeasureJob

    tr = set_tracer(Tracer())
    try:
        jobs = [
            MeasureJob(
                fn=lambda _x: time.sleep(0.002),
                args=(1,),
                repeats=1,
                warmup=0,
                candidate=("blk",),
            )
            for _ in range(4)
        ]
        ex = DeviceParallelExecutor(devices=[None, None], max_workers=2)
        out = ex.run(jobs)
        assert len(out) == 4
        trials = [r for r in tr.records() if r.name == "trial"]
        assert len(trials) == 4
        assert all(r.args["candidate"] == "('blk',)" for r in trials)
        # two workers -> the spans land on (at most) two distinct tracks
        assert 1 <= len({r.tid for r in trials}) <= 2
    finally:
        set_tracer(None)


def test_session_stage_spans():
    from repro.core.planner import SubsetSpace
    from repro.offload import OffloadSession

    space = SubsetSpace(lambda subset: (lambda x: x), ["blk"])
    tr = Tracer()
    session = OffloadSession(space, args=(1,), repeats=1, tracer=tr)
    session.run(verify=True)
    stages = [r.name for r in tr.records() if r.name.startswith("stage:")]
    assert stages == [
        "stage:analyze", "stage:discover", "stage:plan",
        "stage:verify", "stage:commit",
    ]


# -- serve-engine integration -------------------------------------------------


@pytest.fixture(scope="module")
def traced_engine():
    """One small engine, 3 requests served under an enabled tracer."""
    from repro.configs import get_config
    from repro.serve import Request, ServeEngine

    cfg = get_config("llama3.2-1b").reduced()
    engine = ServeEngine(
        cfg, n_slots=2, max_len=64, seed=0, tracer=Tracer()
    )
    for i in range(3):
        engine.submit(Request([1 + i, 2, 3, 4, 5], max_new_tokens=4))
    completions = engine.run_until_idle(max_steps=500)
    return engine, completions


def test_engine_request_lifecycle_spans(traced_engine, tmp_path):
    engine, completions = traced_engine
    assert len(completions) == 3
    per_request = {}
    for rec in engine.tracer.records():
        req = (rec.args or {}).get("request")
        if req is not None:
            per_request.setdefault(req, set()).add(rec.name)
    assert set(per_request) == {0, 1, 2}
    for kinds in per_request.values():
        # the acceptance gate: every request's track carries its whole
        # lifecycle, at least queue / kv-alloc / prefill / decode
        assert {"queue", "kv-alloc", "prefill", "decode"} <= kinds
        assert "complete" in kinds
    path = tmp_path / "engine_trace.json"
    engine.tracer.write_chrome(str(path))
    assert timeline.validate(timeline.load_events(str(path))) == []


def test_engine_metrics_parity_with_telemetry(traced_engine):
    """The registry counters and the legacy PhaseTelemetry aggregates are
    two views of the same observations — they must agree exactly."""
    engine, completions = traced_engine
    reg = engine.registry
    for phase in ("prefill", "decode"):
        tele = engine.telemetry[phase]
        calls = reg.get("serve_phase_calls_total").labels(phase=phase)
        seconds = reg.get("serve_phase_seconds_total").labels(phase=phase)
        tokens = reg.get("serve_phase_tokens_total").labels(phase=phase)
        assert calls.value == tele.calls
        assert seconds.value == pytest.approx(tele.seconds)
        assert tokens.value == tele.tokens
    assert reg.get("serve_requests_submitted_total").value == 3
    assert reg.get("serve_requests_completed_total").value == 3
    assert reg.get("serve_tokens_generated_total").value == sum(
        len(c.tokens) for c in completions
    )
    # the step histogram is the monitor's own observations, written through
    assert reg.get("serve_step_seconds").value == engine.monitor.steps
    text = reg.render_prometheus()
    assert 'serve_phase_calls_total{phase="decode"}' in text
    assert 'serve_step_seconds_bucket{le="+Inf"}' in text


def test_engine_ttft_admitted_and_queue_wait(traced_engine):
    _, completions = traced_engine
    for c in completions:
        assert c.admitted_at is not None
        assert c.queue_wait >= 0.0
        assert 0.0 <= c.ttft_admitted <= c.ttft
        assert c.ttft == pytest.approx(c.queue_wait + c.ttft_admitted)


def test_engine_program_stats(traced_engine):
    engine, _ = traced_engine
    stats = engine.programs.stats()
    assert stats["decode"]["calls"] > 0
    assert stats["decode"]["retraces"] == 0
    assert stats["decode"]["compile_seconds"] > 0
    # each first call per signature ran inside a live compile span
    compiles = [
        r for r in engine.tracer.records() if r.name == "serve.compile"
    ]
    assert sorted({r.args["program"] for r in compiles}) == sorted(
        name for name, st in stats.items() if st["calls"]
    )
    assert len(compiles) == sum(st["signatures"] for st in stats.values())


def test_engine_reset_stats_clears_obs_state():
    from repro.configs import get_config
    from repro.serve import Request, ServeEngine

    cfg = get_config("llama3.2-1b").reduced()
    engine = ServeEngine(
        cfg, n_slots=2, max_len=64, seed=0, tracer=Tracer()
    )
    engine.submit(Request([1, 2, 3], max_new_tokens=2))
    engine.run_until_idle(max_steps=100)
    assert len(engine.tracer) > 0
    engine.reset_stats()
    assert len(engine.tracer) == 0
    assert engine.registry.get("serve_requests_completed_total").value == 0
    # post-reset traffic still feeds the same child handles
    engine.submit(Request([1, 2, 3], max_new_tokens=2))
    engine.run_until_idle(max_steps=100)
    assert engine.registry.get("serve_requests_completed_total").value == 1
    assert engine.telemetry["decode"].calls == (
        engine.registry.get("serve_phase_calls_total")
        .labels(phase="decode").value
    )


def test_engine_disabled_tracer_records_nothing():
    """The default engine inherits the disabled process tracer: the run
    must produce zero records and never flip it on (the zero-overhead
    configuration the serving benchmark ships with)."""
    from repro.configs import get_config
    from repro.serve import Request, ServeEngine

    cfg = get_config("llama3.2-1b").reduced()
    engine = ServeEngine(cfg, n_slots=2, max_len=64, seed=0)
    assert engine.tracer.enabled is False
    engine.submit(Request([1, 2, 3], max_new_tokens=2))
    completions = engine.run_until_idle(max_steps=100)
    assert len(completions) == 1
    assert len(engine.tracer) == 0
    # metrics still work — the registry is independent of tracing
    assert engine.registry.get("serve_requests_completed_total").value == 1


# -- the profiler bridge ------------------------------------------------------


@pytest.fixture
def annotations(monkeypatch):
    """``jax.profiler.TraceAnnotation`` replaced by a recorder of its
    enters and exits."""
    import jax.profiler

    log = []

    class Recorder:
        def __init__(self, name):
            self.name = name
            log.append(("new", name))

        def __enter__(self):
            log.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self.name))
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return log


@pytest.mark.parametrize("enabled", [True, False], ids=["enabled", "disabled"])
def test_profiler_bridge_follows_enabled(annotations, enabled):
    tr = Tracer(enabled=enabled)
    with tr.span("outer"):
        with tr.span("inner", step=1):
            pass
    # retroactive records and instants stay on the ring only
    tr.add_span("retro", 0.0, 1.0)
    tr.event("instant")
    if not enabled:
        assert annotations == [] and len(tr) == 0
        return
    assert annotations == [
        ("new", "outer"), ("enter", "outer"),
        ("new", "inner"), ("enter", "inner"),
        ("exit", "inner"), ("exit", "outer"),
    ]
    assert [r.name for r in tr.records()] == [
        "inner", "outer", "retro", "instant"
    ]


def test_profiler_bridge_exits_on_error(annotations):
    tr = Tracer()
    with pytest.raises(KeyError):
        with tr.span("failing"):
            raise KeyError("x")
    assert annotations[-1] == ("exit", "failing")
    assert [r.name for r in tr.records()] == ["failing"]


# -- engine spans, token read times and block scopes --------------------------

#: the scopes the serving programs open besides the function blocks
PROGRAM_SCOPES = ("kv_write", "head", "mlp", "sample")


@pytest.fixture(scope="module")
def paged_traced_engine():
    """A paged engine with chunked prefill under an enabled tracer, each
    step timed by the caller: [(call time, return time, events)]."""
    from repro.configs import get_config
    from repro.serve import Request, ServeEngine

    cfg = get_config("llama3.2-1b").reduced()
    engine = ServeEngine(
        cfg, n_slots=2, max_len=64, page_size=8, prefill_chunk=16, seed=0,
        tracer=Tracer(),
    )
    for prompt in ([1, 2, 3, 4, 5], list(range(1, 21)), [7, 8, 9]):
        engine.submit(Request(prompt, max_new_tokens=6))
    steps = []
    while engine.scheduler.has_work:
        t_call = time.perf_counter()
        events = engine.step()
        steps.append((t_call, time.perf_counter(), events))
    return engine, steps


def _serve_spans(engine, name):
    return [r for r in engine.tracer.records() if r.name == name]


@pytest.mark.parametrize("name,count", [
    ("serve.step", lambda e, ev: e.stats.steps),
    ("serve.admit", lambda e, ev: e.stats.steps),
    ("serve.kv_health", lambda e, ev: e.stats.steps),
    ("serve.decode", lambda e, ev: e.telemetry["decode"].calls),
    ("serve.decode_wait", lambda e, ev: e.telemetry["decode"].calls),
    ("serve.emit", lambda e, ev: e.telemetry["decode"].calls),
    ("serve.chunk", lambda e, ev: e.stats.prefill_chunks),
    # one unchunked prompt each: the 5- and 3-token requests
    ("serve.prefill", lambda e, ev: 2),
    # one insert and one blocking first-token read per first token
    ("serve.insert", lambda e, ev: sum(
        1 for x in ev if getattr(x, "phase", None) == "prefill")),
    ("serve.first_token", lambda e, ev: sum(
        1 for x in ev if getattr(x, "phase", None) == "prefill")),
])
def test_engine_serve_span_counts(paged_traced_engine, name, count):
    engine, steps = paged_traced_engine
    events = [ev for _, _, evs in steps for ev in evs]
    assert engine.stats.prefill_chunks == 2  # the 20-token prompt
    assert len(_serve_spans(engine, name)) == count(engine, events) > 0


def test_engine_serve_spans_nest_in_steps(paged_traced_engine):
    engine, steps = paged_traced_engine
    records = engine.tracer.records()
    step_spans = sorted(
        (r for r in records if r.name == "serve.step"), key=lambda r: r.t0
    )
    assert [r.args["step"] for r in step_spans] == list(
        range(1, len(steps) + 1)
    )
    inner = [
        r for r in records
        if r.name.startswith("serve.") and r.name != "serve.step"
    ]
    assert {r.name for r in inner} >= {
        "serve.pages", "serve.decode", "serve.compile",
    }
    engine_track = step_spans[0].tid
    for r in inner:
        assert r.tid == engine_track
        assert any(s.t0 <= r.t0 and r.t1 <= s.t1 for s in step_spans), r.name
    # each step's decode dispatch precedes its blocking read
    decodes = sorted(_serve_spans(engine, "serve.decode"), key=lambda r: r.t0)
    waits = sorted(
        _serve_spans(engine, "serve.decode_wait"), key=lambda r: r.t0
    )
    assert all(d.t1 <= w.t0 for d, w in zip(decodes, waits))
    # the engine track carries no retroactive decode span any more; each
    # request's track keeps its mirrored one
    retro = [r for r in records if r.name == "decode"]
    assert retro and all(r.tid != engine_track for r in retro)


@pytest.mark.parametrize("traced", [True, False], ids=["traced", "untraced"])
def test_token_read_times(paged_traced_engine, traced):
    from repro.configs import get_config
    from repro.serve import Request, ServeEngine, Token

    if traced:
        _, steps = paged_traced_engine
    else:
        cfg = get_config("llama3.2-1b").reduced()
        engine = ServeEngine(cfg, n_slots=2, max_len=64, page_size=8, seed=0)
        for prompt in ([1, 2, 3], [4, 5, 6, 7]):
            engine.submit(Request(prompt, max_new_tokens=3))
        steps = []
        while engine.scheduler.has_work:
            t_call = time.perf_counter()
            events = engine.step()
            steps.append((t_call, time.perf_counter(), events))
    last = 0.0
    for t_call, t_return, events in steps:
        for tok in (e for e in events if isinstance(e, Token)):
            assert t_call <= tok.at <= t_return
            assert tok.at >= last
            last = tok.at


#: instructions that run no device operation of their own
FREE = re.compile(r" (constant|parameter|get-tuple-element|tuple|bitcast)\(")


def _computations(hlo_text):
    """computation name -> its instruction lines."""
    comps, current = {}, None
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            current = line.split()[1 if line.startswith("ENTRY") else 0]
            comps[current.lstrip("%")] = []
        elif current and line.startswith("  "):
            comps[current.lstrip("%")].append(line)
    return comps


def test_op_scopes_on_the_paged_decode_program(paged_traced_engine):
    from repro.core import blocks
    from repro.obs import op_scopes

    engine, _ = paged_traced_engine
    texts = engine.programs.compiled_texts(["decode"])
    (text,) = texts["jit_decode_fn"]
    scopes = op_scopes(text, blocks.registry.blocks() + list(PROGRAM_SCOPES))
    assert set(PROGRAM_SCOPES) | {"paged_attention", "rmsnorm"} <= set(
        scopes.values()
    )
    comps = _computations(text)
    walks = []
    for line in (x for lines in comps.values() for x in lines):
        m = re.search(r"%([\w.\-]+) = .* while\(.*body=%([\w.\-]+)", line)
        if m and scopes[m.group(1)] == "paged_attention":
            walks.append(m.group(2))
    assert len(walks) >= 1  # one walk reads K and V together
    for body in walks:
        # constants and tuple plumbing carry whichever op XLA merged or
        # widened them from; they take no device time
        named = [
            line for line in comps[body]
            if "op_name=" in line and not FREE.search(line)
        ]
        assert named
        for line in named:
            name = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)", line).group(1)
            assert scopes[name] == "paged_attention", line


def test_compiled_texts_one_per_signature(paged_traced_engine):
    engine, _ = paged_traced_engine
    stats = engine.programs.stats()
    texts = engine.programs.compiled_texts()
    assert sum(len(t) for t in texts.values()) == sum(
        st["signatures"] for st in stats.values()
    )
    assert set(texts) >= {"jit_decode_fn", "jit_extend_fn"}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/while/body/paged_attention/while/body/gather", "paged_attention"),
    ("jit(f)/head/rmsnorm/mul", "rmsnorm"),  # the innermost scope wins
    ("jit(f)/head/dot_general", "head"),
    ("jit(f)/while/body/add", "other"),
    ("jit(f)/paged_attention_extra/add", "other"),  # whole components only
    (None, "other"),
])
def test_op_scopes_innermost_name(op_name, scope):
    from repro.obs import module_name, op_scopes

    meta = f', metadata={{op_name="{op_name}" stack_frame_id=3}}' if op_name else ""
    text = (
        "HloModule jit_f, entry_computation_layout={()->f32[]}\n\n"
        "ENTRY %main.1 () -> f32[] {\n"
        f"  ROOT %fusion.7 = f32[] fusion(), kind=kLoop{meta}\n"
        "}\n"
    )
    assert module_name(text) == "jit_f"
    assert op_scopes(text, ["paged_attention", "head", "rmsnorm"]) == {
        "fusion.7": scope
    }
