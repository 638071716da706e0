"""One run of one cell: build, warm up, lead in, measure, check.

``run()`` is the whole of a benchmark run short of the command line:
``run.py`` adds the chip's compile cache and prints the result.  Tests
drive ``run()`` on the CPU with ``require_tpu=False`` and a toy cell.
"""

from __future__ import annotations

import collections
import gc
import importlib.util
import json
import pathlib
import sys
import tempfile
import time
import types

from bench import correct, traffic, trace_reduce, weights, window, work

HERE = pathlib.Path(__file__).resolve().parent

#: names of the host annotations around the harness's own calls
SUBMIT, STEP, SLEEP, BOOK = trace_reduce.HOST_SPANS

#: a traced run traces the last seconds of its window, not all of it: a
#: trace of every operation of a whole window would take longer to write
#: and read than the run has
TRACE_SECONDS = 5.0


def use_compile_cache(root: pathlib.Path) -> None:
    """Keep JAX's persistent compilation cache at a fixed directory inside
    the checkout, every program in it: only a cell's first run there
    compiles, and nothing is shared with another checkout."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def log(t_start: float, what: str) -> None:
    """One line on standard error: seconds since the process started."""
    print(f"bench {time.perf_counter() - t_start:8.2f}s {what}", file=sys.stderr,
          flush=True)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(root: pathlib.Path, workload: str):
    """(benchmark, cell, configuration, mix) for a workload name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    config = json.loads(
        (root / "bench" / "configs" / f"{cell['config']}.json").read_text()
    )
    mix = traffic.load_mix(cell["traffic"], root / "bench")
    return bench, cell, config, mix


def metrics_for(bench: dict, cell: dict, trace: bool) -> list:
    """The metric entries this cell reports: its end-to-end metrics, or,
    traced, its per-layer metrics (a metric without ``workloads`` goes
    wherever the end-to-end metric it moves is reported)."""

    def applies(m):
        return cell["name"] in m["workloads"] if "workloads" in m else True

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [
        m for m in bench["per_layer"]
        if (cell["name"] in m["workloads"] if "workloads" in m
            else m["moves"] in moved)
    ]


def warm_lengths(config: dict, mix: dict) -> list:
    """One prompt length per prefill bucket the mix can reach: from its
    shortest prompt to its longest context (a preempted request
    re-prefills prompt and served tokens)."""
    bucket = config["engine"]["prefill_bucket"]
    lo = -(-mix["prompt"]["min"] // bucket) * bucket
    hi = min(traffic.context_bound(mix), config["engine"]["max_len"] - 2)
    return list(range(lo, hi + 1, bucket)) + ([hi] if hi % bucket else [])


def judge(compared: dict, limits: dict, prefix: str = "") -> tuple:
    """({name: {value, limit}}, ok) for the compared numbers named
    ``prefix + name``: ok when each is there and none is over its limit."""
    checks = {name: {"value": compared.get(prefix + name), "limit": limit}
              for name, limit in limits.items()}
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    return checks, ok


def counters(engine) -> dict:
    tele = engine.telemetry
    programs = engine.programs.stats()
    return {
        "decode_calls": tele["decode"].calls,
        "decode_seconds": tele["decode"].seconds,
        "decode_tokens": tele["decode"].tokens,
        "prefill_calls": tele["prefill"].calls,
        "prefill_seconds": tele["prefill"].seconds,
        "prefill_tokens": tele["prefill"].tokens,
        "signatures": sum(p["signatures"] for p in programs.values()),
        "preemptions": engine.scheduler.preemptions,
        "waiting": len(engine.scheduler.waiting),
    }


def drive(engine, schedule, lead_in: float, seconds: float, trace_dir=None):
    """Lead in, then measure for ``seconds``: an open loop that submits
    each request when it is due and steps the engine between arrivals.
    With ``trace_dir``, the profiler traces the window's last
    ``TRACE_SECONDS`` (the caller stops it once the window has closed).
    Returns the :class:`window.Record` and the finished requests
    [(request id, prompt, served tokens)] of the window."""
    import jax

    from repro.serve import Completion, Request, Token

    annotate = jax.profiler.TraceAnnotation
    rec = window.Record()
    pending = collections.deque(schedule)
    finished = []
    pool = engine.kv.pool
    in_window = False
    window_span = None
    t0 = time.perf_counter()
    rec.start = t0 + lead_in
    while True:
        now = time.perf_counter()
        if not in_window and now >= rec.start:
            rec.counters = {k: [v, None] for k, v in counters(engine).items()}
            rec.start = now
            in_window = True
        if in_window and now >= rec.start + seconds:
            break
        if (trace_dir is not None and window_span is None and in_window
                and now >= rec.start + seconds - TRACE_SECONDS):
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            window_span = annotate("window")
            window_span.__enter__()
            rec.trace_from = len(rec.steps)
        with annotate(SUBMIT):
            while pending and t0 + pending[0].due_s <= now:
                planned = pending.popleft()
                due = t0 + planned.due_s
                try:
                    rid = engine.submit(
                        Request(planned.prompt, planned.max_new_tokens)
                    )
                except ValueError:
                    rec.refused += rec.start <= due
                    continue
                rec.due[rid] = due
                rec.submitted[rid] = now
                rec.prompt_len[rid] = len(planned.prompt)
        if not engine.scheduler.has_work:
            nxt = t0 + pending[0].due_s if pending else now + seconds
            bound = rec.start if not in_window else rec.start + seconds
            with annotate(SLEEP):
                time.sleep(max(0.0, min(nxt, bound) - now))
            continue
        with annotate(STEP):
            events = engine.step()
        t = time.perf_counter()
        with annotate(BOOK):
            decode_ctx, prefill_ctx = [], []
            for ev in events:
                if isinstance(ev, Token):
                    rec.tokens.setdefault(ev.request_id, []).append(
                        (t, ev.index, ev.phase)
                    )
                    ctx = rec.prompt_len[ev.request_id] + ev.index
                    (decode_ctx if ev.phase == "decode" else prefill_ctx).append(ctx)
                elif isinstance(ev, Completion):
                    rec.finished[ev.request_id] = t
                    rec.admitted[ev.request_id] = ev.admitted_at
                    if in_window:
                        finished.append((ev.request_id, ev.prompt, ev.tokens))
            if in_window:
                rec.steps.append((decode_ctx, prefill_ctx))
                rec.kv_used.append(pool.used_pages / pool.n_pages)
    rec.end = now
    if window_span is not None:
        window_span.__exit__(None, None, None)
    for state in engine.scheduler.active.values():
        rec.admitted[state.request_id] = state.admitted_at
    for k, v in counters(engine).items():
        rec.counters[k][1] = v
    return rec, finished


def run(
    root: pathlib.Path,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    require_tpu: bool = True,
    control: bool = False,
    fault=None,
) -> dict:
    """One run; returns the result line as a dict (``checks`` last).

    ``fault`` (tests only) is called with the engine before the warm-up,
    to break the timed path underneath the harness.  ``control`` also
    reads the control's gaps on the same sample and judges them by the
    same limits, under ``control`` in the result (not part of a
    benchmark run).
    """
    import jax

    bench, cell, config, mix = load_cell(root, workload)
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and (platform != "tpu" or len(devices) < cell["chips"]):
        raise NoChip(
            f"cell {workload} needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devices)} {platform} device(s)"
        )
    family = config["family"]
    adapter = load_module(root / "bench" / "adapters" / f"{family}.py",
                          f"bench_adapter_{family}")
    ref = load_module(root / "bench" / "reference" / f"{family}.py",
                      f"bench_reference_{family}")

    log(t_start, "imports done")
    cfg = adapter.arch_config(config)
    w = weights.make(ref.layout(config), seed, config["weights"],
                     config["dtypes"]["param"])
    engine = adapter.engine(config, cfg, adapter.program_params(w, cfg), seed)
    del w
    if fault is not None:
        fault(engine)
    schedule = traffic.generate(mix, config["vocab_size"], seed, root / "bench",
                                slots=config["engine"]["n_slots"])
    log(t_start, "weights, engine and schedule made")

    from repro.serve import Request

    warm_rng = weights.rng(seed, "warmup")
    for length in warm_lengths(config, mix):
        engine.submit(Request(
            warm_rng.integers(0, config["vocab_size"], length), max_new_tokens=2
        ))
    engine.run_until_idle()
    log(t_start, f"warm-up done: {engine.programs.stats()}")

    with tempfile.TemporaryDirectory() as tmp:
        rec, finished = drive(
            engine, schedule, mix["lead_in_s"], seconds,
            trace_dir=tmp if trace else None,
        )
        setup_s = rec.start - t_start
        reduced = None
        if trace:
            jax.profiler.stop_trace()
            reduced = trace_reduce.reduce(trace_reduce.find(tmp))
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devices[: cell["chips"]]
    )
    del engine
    gc.collect()
    log(t_start, f"window closed; device bytes in use after freeing the "
        f"engine: {(devices[0].memory_stats() or {}).get('bytes_in_use')}")

    seqs = correct.sample(finished, seed)
    length = correct.reference_length(
        min(traffic.context_bound(mix), config["engine"]["max_len"])
    )
    compared = correct.gaps(ref, config, seed, seqs, length, control=control)
    log(t_start, f"reference compared {compared['tokens_compared']} tokens "
        f"of {len(seqs)} requests")

    ctx = types.SimpleNamespace(
        rec=rec, config=config, mix=mix, setup_s=setup_s, trace=reduced,
        peaks=work.peaks(devices[0].device_kind) if platform == "tpu" else None,
    )
    metrics = {}
    for m in metrics_for(bench, cell, trace):
        reader = load_module(root / "bench" / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # each compared number beside its limit (the configuration's
    # ``correct.limits``): a run is correct when none is over its limit;
    # a window that finished no request has no number (null) and fails
    limits = config["correct"]["limits"]
    checks, ok = judge(compared, limits)
    checks["window_compiles"] = {
        "value": window.delta(rec, "signatures"), "limit": 0,
    }
    checks["tokens_compared"] = {
        "value": compared["tokens_compared"],
        "limit": config["correct"]["min_tokens_compared"],
    }
    ok = (ok and checks["window_compiles"]["value"] == 0
          and compared["tokens_compared"] >= checks["tokens_compared"]["limit"])
    served = {rid for rid, toks in rec.tokens.items()
              if any(rec.inside(t) for t, _, _ in toks)}
    device = {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": cell["chips"],
        "memory_peak_bytes": peak,
    }
    result = {
        "correct": bool(ok),
        "attempted": len(served | set(window.due_in_window(rec))),
        "failed": rec.refused,
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["top_ops"],
            "idle_gaps": reduced["idle_by_host"],
        }
    # every number the comparison read, limited or not (the control's
    # with ``control``)
    result["compared"] = compared
    if control:
        # the control put in the program's place, judged by the same limits
        ctl_checks, ctl_ok = judge(compared, limits, prefix="control_")
        result["control"] = {"correct": ctl_ok, "checks": ctl_checks}
    late = window.p95(window.late_s(rec))
    # diagnostics beside the contract's keys: how the window went
    result["window"] = {
        "seconds": rec.seconds,
        "setup_s": setup_s,
        "tokens": window.delivered_tokens(rec),
        "finished": sum(1 for t in rec.finished.values() if rec.inside(t)),
        "preemptions": window.delta(rec, "preemptions"),
        "waiting": rec.counters["waiting"],
        "late_submit_p95_ms": None if late is None else 1e3 * late,
    }
    result["checks"] = checks
    print(f"window: {json.dumps(result['window'])}", file=sys.stderr)
    return result
