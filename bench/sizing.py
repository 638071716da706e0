"""Size a configuration's engine from compiled memory analysis, without a
chip: compile the serving programs for a described TPU v5e and report
what they hold, then the slots and pages that fit.

    JAX_PLATFORMS=cpu PYTHONPATH=.:src python bench/sizing.py \\
        --config stablelm-1.6b --mix chat-backlog --slots 32,48,64

It prints the prefill program's memory analysis at the mix's longest
context; then, per candidate slot count, the decode program's at two pool
sizes, and the page pool that what the parameters and the largest
program leave of the allocator's limit can hold.  Slots are that pool's tokens over the mix's
mean resident context, rounded down to a multiple of 8.

On the chip, ``--measure SLOTS:PAGES`` builds the engine at that size
instead, serves a prompt at the mix's longest prefill bucket beside a
full batch of short ones, and prints the chip's measured peak, its
allocator limit and the full-batch decode step.  A process's peak never
falls, so each size is a process of its own:

    for s in 32:1104 48:1656; do python3 bench/sizing.py \
        --config stablelm-1.6b --mix chat-backlog --measure $s; done

Not part of a benchmark run; its output is recorded in PERF.md.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

#: allocator limit of one TPU v5e chip (``bytes_limit`` of
#: ``memory_stats()``, measured on the chip), and the share kept free
BYTES_LIMIT = int(15.75 * 2**30)
RESERVED = 258 * 2**20  # the compiler's own reservation
MARGIN = 0.05


def analysis(compiled) -> dict:
    m = compiled.memory_analysis()
    return {
        "argument": m.argument_size_in_bytes,
        "output": m.output_size_in_bytes,
        "alias": m.alias_size_in_bytes,
        "temp": m.temp_size_in_bytes,
    }


def main() -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--slots", default="32,48,64")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--check", default="",
                    help="slots:pages,... to compile as they stand")
    ap.add_argument("--measure", default="",
                    help="slots:pages to build and measure on the chip")
    args = ap.parse_args()
    if args.measure:
        measure(args.config, args.mix, *(int(x) for x in args.measure.split(":")))
        return

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import traffic, work
    from bench.harness import load_module
    from repro.models import lm

    config = json.loads((ROOT / "bench/configs" / f"{args.config}.json").read_text())
    if args.layers:
        config["num_hidden_layers"] = args.layers
    mix = traffic.load_mix(args.mix)
    adapter = load_module(ROOT / "bench/adapters" / f"{config['family']}.py", "a")
    ref = load_module(ROOT / "bench/reference" / f"{config['family']}.py", "r")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype), sharding=dev)

    def abstract(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    cfg = adapter.arch_config(config)
    eng = config["engine"]
    params = adapter.program_params(
        {k: sds(s, config["dtypes"]["param"]) for k, (s, _) in ref.layout(config).items()},
        cfg,
    )
    param_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    max_pages = -(-eng["max_len"] // eng["page_size"])
    page_bytes = work.kv_bytes_per_token(config) * eng["page_size"]
    resident = traffic.mean_resident(mix)
    out = {"config": args.config, "layers": config["num_hidden_layers"],
           "param_bytes": param_bytes, "page_bytes": page_bytes,
           "mean_resident_tokens": resident, "bytes_limit": BYTES_LIMIT}

    def programs(n_slots, n_pages):
        small = dict(eng, n_slots=n_slots, n_pages=1)
        e = adapter.engine(dict(config, engine=small), cfg, params, 0)
        cache = abstract(jax.eval_shape(lambda: lm.init_cache(
            cfg, n_slots, eng["max_len"], page_size=eng["page_size"],
            n_pages=n_pages)))
        return e, cache

    i32 = lambda *s: sds(s, "int32")  # noqa: E731
    f32 = lambda *s: sds(s, "float32")  # noqa: E731

    def decode(n_slots, n_pages):
        e, cache = programs(n_slots, n_pages)
        c = e.programs.records["decode"].fn.lower(
            params, i32(n_slots, 1), cache, i32(n_slots, max_pages),
            i32(n_slots), i32(n_slots), f32(n_slots), i32(n_slots),
        ).compile()
        return analysis(c)

    if args.check:
        for pair in args.check.split(","):
            n, pages = (int(x) for x in pair.split(":"))
            d = decode(n, pages)
            total = RESERVED + d["argument"] + d["temp"]
            print(json.dumps({"slots": n, "n_pages": pages, **d,
                              "total": total,
                              "fits": total <= (1 - MARGIN) * BYTES_LIMIT}),
                  flush=True)
        return

    # the pool is an argument of every program, and the decode program's
    # temporaries grow with it (its layer scan writes a new pool); two
    # probes give that growth, the rest of the temporaries stay put
    lo, hi = 256, 512
    e, cache = programs(8, lo)
    bucket = eng["prefill_bucket"]
    longest = -(-min(traffic.context_bound(mix), eng["max_len"]) // bucket) * bucket
    pre = e.programs.records["prefill"].fn.lower(
        params, i32(1, longest), i32(), i32(), i32(), f32(), i32()
    ).compile()
    prefill = analysis(pre)
    print(json.dumps({"prefill_tokens": longest, **prefill}), flush=True)
    budget = (1 - MARGIN) * BYTES_LIMIT - RESERVED - param_bytes
    best = None
    for n in [int(x) for x in args.slots.split(",")]:
        a, b = decode(n, lo), decode(n, hi)
        per_page = (b["temp"] - a["temp"]) / (hi - lo)
        fixed = a["temp"] - per_page * lo
        pages = int(min(
            (budget - fixed) / (page_bytes + per_page),
            (budget - prefill["temp"] - prefill["output"]) / page_bytes,
        ))
        slots = int(pages * eng["page_size"] / resident) // 8 * 8
        row = {"slots": n, "decode_temp_fixed": fixed,
               "decode_temp_per_page": per_page, "probe": [a, b],
               "n_pages": pages, "pool_bytes": pages * page_bytes,
               "slots_fit": slots}
        print(json.dumps(row), flush=True)
        if slots >= n:
            best = {"n_slots": n, "n_pages": pages}
    print(json.dumps({**out, "choice": best}), flush=True)


def measure(name: str, mix_name: str, n_slots: int, n_pages: int) -> None:
    """Build the engine at ``n_slots`` and ``n_pages`` on the chip, serve
    the longest prefill bucket and a full decode batch, print the peak."""
    import time

    import jax

    from bench import harness, traffic, weights
    from repro.serve import Request

    harness.use_compile_cache(ROOT)
    config = json.loads((ROOT / "bench/configs" / f"{name}.json").read_text())
    config["engine"] = dict(config["engine"], n_slots=n_slots, n_pages=n_pages)
    mix = traffic.load_mix(mix_name, ROOT / "bench")
    adapter = harness.load_module(ROOT / "bench/adapters" / f"{config['family']}.py", "a")
    ref = harness.load_module(ROOT / "bench/reference" / f"{config['family']}.py", "r")
    cfg = adapter.arch_config(config)
    w = weights.make(ref.layout(config), 0, config["weights"], config["dtypes"]["param"])
    engine = adapter.engine(config, cfg, adapter.program_params(w, cfg), 0)
    del w
    lengths = harness.warm_lengths(config, mix)
    rng = weights.rng(0, "sizing")
    steps = 8
    engine.submit(Request(rng.integers(0, config["vocab_size"], lengths[-1]),
                          max_new_tokens=steps))
    for _ in range(n_slots - 1):
        engine.submit(Request(rng.integers(0, config["vocab_size"], lengths[0]),
                              max_new_tokens=steps))
    times = []
    while engine.scheduler.has_work:
        t = time.perf_counter()
        engine.step()
        times.append(time.perf_counter() - t)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    print(json.dumps({
        "config": name, "slots": n_slots, "n_pages": n_pages,
        "device_kind": dev.device_kind,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
        "full_batch_step_s": sorted(times[2:])[len(times[2:]) // 2],
    }), flush=True)


if __name__ == "__main__":
    main()
