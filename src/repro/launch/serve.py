"""Serving CLI — a thin driver over :class:`repro.serve.ServeEngine`.

Submits a mixed-length batch of random-token requests and drives the
engine until idle, printing throughput, latency and power telemetry:

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
      --requests 8 --prompt-len 24 --len-jitter 8 --gen 16 --slots 4

Production startup binds previously verified offload plans (committed by
``repro.offload.zoo`` in a verification environment) per phase — prefill
and decode each trace under their own ``zoo:<arch>:<phase>`` plan:

  ... --plan-dir results/plans

``--plan-key`` forces one explicit key for both phases, ``--plan-search``
searches and commits missing zoo plans first (``--executor`` parallelises
the measurement), ``--meter`` adds real power telemetry with
measured/estimated provenance, and ``--sampler`` sets the default policy
(``greedy`` | ``temperature:0.8`` | ``top_k:40:0.8``).
"""

from __future__ import annotations

import argparse
import sys

import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import Tracer
from repro.serve import Request, Sampler, ServeEngine


def percentile(xs: "list[float]", q: float) -> float:
    """Empty-safe quantile of a sample (shared with serve_load.py)."""
    if not xs:
        return float("nan")
    return float(np.percentile(xs, q * 100))


def format_kv_metrics(engine: ServeEngine) -> str:
    """One line of KV-memory health from ``engine.metrics()`` (shared with
    serve_load.py).  Stranded/utilization/fragmentation are means of one
    sample per engine step while requests were resident."""
    m = engine.metrics()
    kv = m["kv"]
    if m["mode"] == "paged":
        return (
            f"kv pool: {kv['n_pages']} x {kv['page_size']}-token pages, "
            f"peak {kv['peak_used_pages']} used "
            f"({100.0 * kv['peak_used_pages'] / kv['n_pages']:.0f}% peak, "
            f"{m['mean_utilization_pct']:.1f}% mean utilization), "
            f"stranded {m['mean_stranded_pct']:.1f}%, "
            f"fragmentation {m['mean_fragmentation_pct']:.1f}%, "
            f"{m['preemptions']} preemptions, "
            f"{m['prefill_chunks']} prefill chunks"
        )
    return (
        f"kv cache: contiguous {m['n_slots']} x {m['max_len']} "
        f"({kv['token_capacity']} tokens reserved worst-case), "
        f"{m['mean_utilization_pct']:.1f}% mean slot utilization, "
        f"stranded {m['mean_stranded_pct']:.1f}% of reserved, "
        f"{m['prefill_chunks']} prefill chunks"
    )


def build_engine(args: argparse.Namespace) -> ServeEngine:
    """Engine construction shared with ``benchmarks/serve_load.py``."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    plan_keys: dict[str, str | None] | str | None = None
    if args.plan_key:
        plan_keys = args.plan_key
    elif args.plan_dir and args.plan_search:
        from repro.offload.zoo import launch_plan_keys

        plan_keys = launch_plan_keys(
            args.plan_dir,
            args.arch,
            ("prefill", "decode"),
            search=True,
            targets=tuple(args.plan_targets.split(",")),
            executor=args.executor,
            meter=args.meter,
        )
    # --trace-out turns the request-lifecycle tracer on for this engine;
    # without it the engine inherits the (disabled) process tracer and
    # tracing costs one attribute check per hot-path site
    tracer = Tracer() if getattr(args, "trace_out", None) else None
    return ServeEngine(
        cfg,
        n_slots=args.slots,
        max_len=args.max_len,
        sampler=Sampler.parse(args.sampler),
        meter=args.meter,
        plan_dir=args.plan_dir,
        plan_keys=plan_keys,
        max_tokens_per_step=args.step_budget,
        prefill_bucket=args.prefill_bucket,
        prefill_chunk=args.prefill_chunk,
        page_size=args.page_size,
        n_pages=args.n_pages,
        decode_impl=args.decode_impl,
        kv_validate=args.kv_validate,
        tracer=tracer,
        seed=args.seed,
        quiet=False,
    )


def write_obs_outputs(engine: ServeEngine, args: argparse.Namespace) -> None:
    """Write the observability artifacts the CLI asked for: a Chrome/
    Perfetto trace (``--trace-out``, loadable at ui.perfetto.dev) and a
    Prometheus text snapshot of the engine registry (``--metrics-out``)."""
    if getattr(args, "trace_out", None):
        engine.tracer.write_chrome(args.trace_out)
        print(f"trace written: {args.trace_out} "
              f"({len(engine.tracer)} records; inspect with "
              f"python -m repro.obs.timeline {args.trace_out})")
    if getattr(args, "metrics_out", None):
        with open(args.metrics_out, "w") as f:
            f.write(engine.registry.render_prometheus())
        print(f"metrics written: {args.metrics_out}")


def make_requests(
    cfg, args: argparse.Namespace, rng: np.random.Generator
) -> list[Request]:
    """Mixed-length random-token trace: prompt/generation lengths jitter
    uniformly around the base values so slots stagger and free at
    different steps (the continuous-batching case, not the static batch)."""
    requests = []
    for _ in range(args.requests):
        plen = max(1, args.prompt_len + int(rng.integers(
            -args.len_jitter, args.len_jitter + 1
        )))
        gen = max(1, args.gen + int(rng.integers(
            -args.gen_jitter, args.gen_jitter + 1
        )))
        prompt = rng.integers(0, cfg.vocab_size, plen).tolist()
        requests.append(Request(prompt, max_new_tokens=gen))
    return requests


def add_engine_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", type=int, default=4,
                    help="KV slots = max concurrent requests")
    ap.add_argument("--max-len", type=int, default=256,
                    help="cache positions per slot (prompt + generation)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sampler", default="greedy",
                    help="default sampling policy: greedy | "
                         "temperature:<t> | top_k:<k>[:<t>]")
    ap.add_argument("--step-budget", type=int, default=None,
                    help="max tokens (prefill + decode) one engine step "
                         "may process — bounds prefill-induced decode "
                         "stalls under bursty arrivals")
    ap.add_argument("--prefill-bucket", type=int, default=None,
                    help="pad prompts to a multiple of this bucket so "
                         "prefill traces are shared across lengths "
                         "(attention-family archs only)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="split prompts longer than this into chunk-sized "
                         "prefill pieces interleaved with decode steps "
                         "(flattens the p99 TTFT spike; attention-family "
                         "archs only)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="block-paged KV cache: tokens per page (default: "
                         "contiguous max_len slots)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="KV pool size in pages (default: capacity-"
                         "equivalent, slots * ceil(max_len/page_size); "
                         "smaller over-commits — preemption reclaims)")
    ap.add_argument("--decode-impl", default="auto",
                    choices=("auto", "xla", "pallas"),
                    help="pin the paged_attention binding for the decode "
                         "hot loop (requires --page-size): xla = rolled "
                         "walk over page blocks, pallas = fused page-walk "
                         "kernel (interpret-mode off-TPU); auto defers to "
                         "the stored decode plan / default preference")
    ap.add_argument("--kv-validate", action="store_true",
                    help="run the repro.analysis page-aliasing sanitizer "
                         "after every page-table mutation (debug mode; "
                         "raises on aliasing or accounting drift)")
    ap.add_argument("--plan-dir", default=None,
                    help="PlanStore directory with verified offload plans")
    ap.add_argument("--plan-key", default=None,
                    help="explicit plan key bound to BOTH phases; default "
                         "is the stored zoo:<arch>:prefill / :decode plans")
    ap.add_argument("--plan-search", action="store_true",
                    help="search+commit missing zoo plans for this arch "
                         "before binding (verification-environment step)")
    ap.add_argument("--plan-targets", default="ref,xla",
                    help="targets --plan-search searches over "
                         "(add 'pallas' on TPU hosts)")
    ap.add_argument("--executor", default="serial",
                    help="measurement executor for --plan-search: serial | "
                         "device-parallel | batched")
    ap.add_argument("--meter", default="none",
                    help="power telemetry: none | auto | time | nvml | "
                         "rapl | psutil | tpu")
    ap.add_argument("--trace-out", default=None,
                    help="enable request-lifecycle tracing and write a "
                         "Chrome/Perfetto trace_event JSON here (inspect "
                         "with ui.perfetto.dev or repro.obs.timeline)")
    ap.add_argument("--metrics-out", default=None,
                    help="write a Prometheus text snapshot of the engine "
                         "metrics registry here after the run")
    ap.add_argument("--envelope", default=None,
                    help="device envelope for capacity checks: a static "
                         "name (a100-40g, cpu-host-16g, tiny-32m, ...) or "
                         "'host' to probe the live device (default)")


def preflight(args: argparse.Namespace) -> int:
    """Static capacity check of the requested deployment — the paper's
    FPGA resource-fit gate applied before engine boot.  Sizes params +
    KV cache from metadata (nothing is materialised, so full-size
    configs check in milliseconds) against ``--envelope`` and refuses to
    proceed when they cannot fit.  Returns a process exit code: 0 fits,
    2 does not."""
    from repro.analysis.resources import plan_serve_capacity

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    plan = plan_serve_capacity(
        cfg,
        n_slots=args.slots,
        max_len=args.max_len,
        page_size=args.page_size,
        n_pages=args.n_pages,
        envelope=args.envelope,
    )
    print(plan.summary())
    if (
        args.prefill_chunk
        and plan.max_prefill_tokens is not None
        and args.prefill_chunk > plan.max_prefill_tokens
    ):
        print(
            f"preflight: note --prefill-chunk {args.prefill_chunk} exceeds "
            f"the activation-headroom bound ({plan.max_prefill_tokens})",
            file=sys.stderr,
        )
    if not plan.fits:
        print(
            f"preflight: FAIL — {plan.arch} with {plan.n_slots} slots x "
            f"{plan.max_len} tokens does not fit {plan.envelope.name}",
            file=sys.stderr,
        )
        return 2
    print("preflight: OK")
    return 0


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser()
    add_engine_args(ap)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--len-jitter", type=int, default=8,
                    help="uniform prompt-length jitter (staggers slots)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--gen-jitter", type=int, default=4)
    ap.add_argument("--max-steps", type=int, default=10_000)
    ap.add_argument("--preflight", action="store_true",
                    help="static capacity check only: size params + KV "
                         "against --envelope and exit (0 fits, 2 not) "
                         "without booting the engine")
    args = ap.parse_args(argv)

    if args.preflight:
        return preflight(args)

    enable_compile_cache()
    engine = build_engine(args)
    rng = np.random.default_rng(args.seed)
    requests = make_requests(engine.cfg, args, rng)
    for request in requests:
        engine.submit(request)
    completions = engine.run_until_idle(max_steps=args.max_steps)

    stats = engine.stats
    assert stats.requests_completed == len(requests), (
        f"{stats.requests_completed}/{len(requests)} requests completed"
    )
    print(f"arch={engine.cfg.name} slots={args.slots} "
          f"requests={len(requests)}")
    for phase in ("prefill", "decode"):
        print(engine.telemetry[phase].summary())
    latencies = [c.latency for c in completions]
    ttfts = [c.ttft for c in completions]
    ttfts_admitted = [c.ttft_admitted for c in completions]
    queue_waits = [c.queue_wait for c in completions]
    print(
        f"latency: p50 {percentile(latencies, 0.5)*1e3:.1f} ms "
        f"p99 {percentile(latencies, 0.99)*1e3:.1f} ms | "
        f"ttft: p50 {percentile(ttfts, 0.5)*1e3:.1f} ms "
        f"p99 {percentile(ttfts, 0.99)*1e3:.1f} ms"
    )
    # ttft folds the scheduler's queue wait in; the admitted variant is
    # the model-side prefill latency with that wait subtracted out
    print(
        f"ttft from admit: p50 {percentile(ttfts_admitted, 0.5)*1e3:.1f} ms "
        f"p99 {percentile(ttfts_admitted, 0.99)*1e3:.1f} ms | "
        f"queue wait: p50 {percentile(queue_waits, 0.5)*1e3:.1f} ms "
        f"p99 {percentile(queue_waits, 0.99)*1e3:.1f} ms"
    )
    print(
        f"continuous batching: {stats.slot_reuses} slot reuses, "
        f"max {stats.max_active} concurrent, {stats.steps} engine steps, "
        f"decode median {engine.monitor.median_step()*1e3:.2f} ms/step"
    )
    print(format_kv_metrics(engine))
    sample = completions[0]
    print(f"sample (request {sample.request_id}):",
          np.asarray(sample.tokens[:16]))
    write_obs_outputs(engine, args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
