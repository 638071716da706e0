"""Poisson load generator for the serving engine.

Drives :class:`repro.serve.ServeEngine` with an open-loop request trace —
exponential inter-arrival times (a Poisson process, the standard serving
load model), mixed prompt/generation lengths — and reports what the
power-saving follow-up work (arXiv:2110.11520) evaluates offloads under:
sustained-load throughput (tok/s), request latency and TTFT percentiles
(p50/p99), and joules/token with measured-vs-estimated provenance.

  PYTHONPATH=src python benchmarks/serve_load.py --arch llama3.2-1b \
      --reduced --requests 16 --rate 8 --meter auto

``--fast`` shrinks the trace for CI (``make serve-bench``).  ``--plan-dir``
binds each phase to its committed zoo plan, so the benchmark measures the
*deployed* offload pattern, not the default bindings.  ``--json-out PATH``
additionally writes a machine-readable snapshot (``BENCH_serve.json``) with
throughput, percentiles (including TTFT-from-admission and queue wait),
energy provenance, per-phase telemetry, engine stats/metrics and the git
revision, so successive runs diff cleanly.  ``--trace-out PATH`` turns the
request-lifecycle tracer on and writes a Chrome/Perfetto trace of the
measured run (``python -m repro.obs.timeline PATH`` summarises it);
``--metrics-out PATH`` dumps the engine's Prometheus registry.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "src")
)

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    add_engine_args,
    build_engine,
    format_kv_metrics,
    make_requests,
    percentile,
    write_obs_outputs,
)
from repro.obs.timeline import span_summary  # noqa: E402
from repro.serve import Request  # noqa: E402


def run_trace(engine, requests, arrivals, max_seconds: float = 600.0):
    """Open-loop drive: submit each request at its arrival time (relative
    to the trace start), stepping the engine in between.  Returns the
    observed makespan in seconds (completions stay on the engine)."""
    t0 = time.perf_counter()
    pending = list(zip(arrivals, requests))
    pending.reverse()  # pop() takes the earliest
    while pending or engine.scheduler.has_work:
        now = time.perf_counter() - t0
        while pending and pending[-1][0] <= now:
            engine.submit(pending.pop()[1])
        if engine.scheduler.has_work:
            engine.step()
        elif pending:
            # idle gap before the next arrival: sleep it off instead of
            # spinning (open-loop arrivals must not be accelerated)
            time.sleep(min(pending[-1][0] - now, 0.05))
        if time.perf_counter() - t0 > max_seconds:
            raise RuntimeError(f"trace still running after {max_seconds}s")
    return time.perf_counter() - t0


def git_sha() -> str:
    """Revision stamp for the snapshot; "unknown" outside a git checkout."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
    except Exception:  # noqa: BLE001 — the snapshot is still useful
        return "unknown"


def snapshot(engine, args, makespan, completions) -> dict:
    """The machine-readable result record ``--json-out`` writes."""
    stats = engine.stats
    gen_tokens = sum(len(c.tokens) for c in completions)
    latencies = [c.latency for c in completions]
    ttfts = [c.ttft for c in completions]
    ttfts_admitted = [c.ttft_admitted for c in completions]
    queue_waits = [c.queue_wait for c in completions]
    phases = {}
    for phase in ("prefill", "decode"):
        t = engine.telemetry[phase]
        phases[phase] = {
            "calls": t.calls,
            "seconds": t.seconds,
            "tokens": t.tokens,
            "tokens_per_second": t.tokens_per_second,
            "joules": t.joules,
            "joules_per_token": t.joules_per_token,
            "provenance": t.provenance,
        }
    joules = (
        (engine.telemetry["prefill"].joules or 0.0)
        + (engine.telemetry["decode"].joules or 0.0)
        if any(engine.telemetry[p].joules is not None
               for p in ("prefill", "decode"))
        else None
    )
    # prefill-vs-decode split of the metered phase time — where the
    # engine's compute actually went, independent of queueing
    phase_seconds = {
        p: engine.telemetry[p].seconds for p in ("prefill", "decode")
    }
    total_phase = sum(phase_seconds.values())
    spans = None
    if engine.tracer.enabled and len(engine.tracer):
        spans = span_summary(engine.tracer.to_chrome()["traceEvents"])
    return {
        "schema": 2,
        "benchmark": "serve_load",
        "git_sha": git_sha(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "arch": engine.cfg.name,
        "reduced": bool(args.reduced),
        "trace": {
            "requests": args.requests,
            "rate_per_s": args.rate,
            "prompt_len": args.prompt_len,
            "len_jitter": args.len_jitter,
            "gen": args.gen,
            "gen_jitter": args.gen_jitter,
            "seed": args.seed,
            "fast": bool(args.fast),
        },
        "engine": {
            "slots": engine.n_slots,
            "max_len": engine.max_len,
            "sampler": args.sampler,
            "meter": args.meter,
            "plan_dir": args.plan_dir,
            "page_size": args.page_size,
            "n_pages": args.n_pages,
            "decode_impl": args.decode_impl,
            "prefill_bucket": args.prefill_bucket,
            "prefill_chunk": args.prefill_chunk,
            "step_budget": args.step_budget,
        },
        "makespan_s": makespan,
        "throughput_tok_s": gen_tokens / makespan if makespan else 0.0,
        "generated_tokens": gen_tokens,
        "latency_ms": {
            "p50": percentile(latencies, 0.5) * 1e3,
            "p99": percentile(latencies, 0.99) * 1e3,
        },
        "ttft_ms": {
            "p50": percentile(ttfts, 0.5) * 1e3,
            "p99": percentile(ttfts, 0.99) * 1e3,
        },
        "ttft_admitted_ms": {
            "p50": percentile(ttfts_admitted, 0.5) * 1e3,
            "p99": percentile(ttfts_admitted, 0.99) * 1e3,
        },
        "queue_wait_ms": {
            "p50": percentile(queue_waits, 0.5) * 1e3,
            "p99": percentile(queue_waits, 0.99) * 1e3,
        },
        "preemptions": stats.preemptions,
        "phase_split": {
            "prefill_s": phase_seconds["prefill"],
            "decode_s": phase_seconds["decode"],
            "prefill_frac": (
                phase_seconds["prefill"] / total_phase if total_phase else 0.0
            ),
        },
        "spans": spans,
        "energy": {
            "joules": joules,
            "joules_per_token": (
                joules / max(gen_tokens, 1) if joules is not None else None
            ),
            "provenance": (
                engine.telemetry["decode"].provenance
                or engine.telemetry["prefill"].provenance
            ),
        },
        "phases": phases,
        "stats": dataclasses.asdict(stats),
        "metrics": engine.metrics(),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    add_engine_args(ap)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=4.0,
                    help="mean arrival rate, requests/second (Poisson)")
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--len-jitter", type=int, default=8)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--gen-jitter", type=int, default=4)
    ap.add_argument("--fast", action="store_true",
                    help="tiny trace on the reduced config (CI smoke)")
    ap.add_argument("--json-out", default=None,
                    help="write a machine-readable snapshot (e.g. "
                         "BENCH_serve.json) next to the printed report")
    ap.add_argument("--preflight", action="store_true",
                    help="static capacity check against --envelope before "
                         "the load run; abort when the config cannot fit")
    args = ap.parse_args()
    if args.fast:
        args.reduced = True
        args.requests = min(args.requests, 8)
        args.prompt_len, args.len_jitter = 12, 4
        args.gen, args.gen_jitter = 8, 3
        args.rate = max(args.rate, 8.0)
        args.slots = min(args.slots, 3)
        args.max_len = min(args.max_len, 64)

    if args.preflight:
        from repro.launch.serve import preflight

        rc = preflight(args)
        if rc != 0:
            raise SystemExit(rc)

    enable_compile_cache()
    engine = build_engine(args)
    rng = np.random.default_rng(args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    requests = make_requests(engine.cfg, args, rng)

    # warmup outside the measured trace: prefill retraces per (padded)
    # prompt length, so compile EVERY length the trace will submit — plus
    # one decode step — or the measured percentiles report XLA compile
    # time instead of serving time; then zero every counter so the warmup
    # never shows up as served traffic
    for length in sorted({len(r.prompt) for r in requests}):
        engine.submit(Request(list(range(1, length + 1)), max_new_tokens=2))
    engine.run_until_idle(max_steps=1000)
    engine.reset_stats()

    makespan = run_trace(engine, requests, arrivals)
    completions = list(engine.completions.values())
    assert len(completions) == args.requests, (
        f"{len(completions)}/{args.requests} requests completed"
    )

    stats = engine.stats
    gen_tokens = sum(len(c.tokens) for c in completions)
    latencies = [c.latency for c in completions]
    ttfts = [c.ttft for c in completions]
    ttfts_admitted = [c.ttft_admitted for c in completions]
    queue_waits = [c.queue_wait for c in completions]
    decode = engine.telemetry["decode"]
    prefill = engine.telemetry["prefill"]

    print(f"arch={engine.cfg.name} slots={engine.n_slots} "
          f"requests={args.requests} rate={args.rate}/s "
          f"makespan={makespan:.2f}s")
    print(prefill.summary())
    print(decode.summary())
    print(f"throughput: {gen_tokens / makespan:.1f} generated tok/s "
          f"({gen_tokens} tokens)")
    print(f"latency: p50 {percentile(latencies, 0.5)*1e3:.1f} ms  "
          f"p99 {percentile(latencies, 0.99)*1e3:.1f} ms")
    print(f"ttft:    p50 {percentile(ttfts, 0.5)*1e3:.1f} ms  "
          f"p99 {percentile(ttfts, 0.99)*1e3:.1f} ms")
    # ttft includes the queue wait; the admitted variant isolates the
    # model-side prefill latency from the scheduler's queueing
    print(f"ttft from admit: "
          f"p50 {percentile(ttfts_admitted, 0.5)*1e3:.1f} ms  "
          f"p99 {percentile(ttfts_admitted, 0.99)*1e3:.1f} ms  "
          f"(queue wait p50 {percentile(queue_waits, 0.5)*1e3:.1f} ms  "
          f"p99 {percentile(queue_waits, 0.99)*1e3:.1f} ms)")
    joules = (
        (prefill.joules or 0.0) + (decode.joules or 0.0)
        if (prefill.joules is not None or decode.joules is not None)
        else None
    )
    if joules is not None:
        prov = decode.provenance or prefill.provenance
        print(f"energy: {joules:.1f} J, "
              f"{joules / max(gen_tokens, 1):.3g} J/token [{prov}]")
    else:
        print("energy: no meter (--meter auto for telemetry)")
    print(f"continuous batching: {stats.slot_reuses} slot reuses, "
          f"max {stats.max_active} concurrent, "
          f"{stats.steps} engine steps")
    print(format_kv_metrics(engine))

    write_obs_outputs(engine, args)
    if args.json_out:
        record = snapshot(engine, args, makespan, completions)
        with open(args.json_out, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        print(f"snapshot written: {args.json_out}")


if __name__ == "__main__":
    main()
