"""Read the program's logit gaps and the control's on the same sample, for
several seeds of a cell, in one process.

    python bench/control.py --workload stablelm-1.6b.chat-backlog \\
        --seeds 101,102,103 --seconds 30

Each seed is a whole benchmark run (its window at the cell's own load)
followed by the reference and the control on the sample it finished, the
control judged by the same limits as the program (``control_correct``,
which has to read false).  The largest program reading of each compared
number over the seeds is the lower reading of its limit, the smallest
control reading its upper reading (PERF.md gives both).
Not part of a benchmark run.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    harness.use_compile_cache(ROOT)

    for seed in [int(s) for s in args.seeds.split(",")]:
        result = harness.run(ROOT, args.workload, seed, args.seconds, False,
                             t_start=time.perf_counter(), control=True)
        print(json.dumps({
            "seed": seed, "correct": result["correct"],
            "control_correct": result["control"]["correct"],
            **result["compared"],
            "window_compiles": result["checks"]["window_compiles"]["value"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        }), flush=True)


if __name__ == "__main__":
    main()
