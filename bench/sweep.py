"""Find the highest arrival rate a cell sustains: run it at several rates
in one process (the chip belongs to one process) and report, per rate,
whether the waiting queue grew through the window.

    python bench/sweep.py --workload granite-3-8b.rag --rates 4,5,6,7,8 \\
        --seconds 20 --seed 1

Not part of a benchmark run; the rate a cell offers is written into its
traffic file as a number, and the sweep that found it into PERF.md.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, traffic

    harness.use_compile_cache(ROOT)

    load_mix = traffic.load_mix
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic.load_mix = lambda name, root=traffic.HERE, r=rate: dict(
            load_mix(name, root), rate_per_s=r
        )
        result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                             False, t_start=time.perf_counter())
        w = result["window"]
        print(json.dumps({
            "rate_per_s": rate,
            "correct": result["correct"],
            **{k: v["value"] for k, v in result["metrics"].items()},
            "waiting_start": w["waiting"][0], "waiting_end": w["waiting"][1],
            "preemptions": w["preemptions"], "finished": w["finished"],
            "late_submit_p95_ms": w["late_submit_p95_ms"],
        }), flush=True)


if __name__ == "__main__":
    main()
