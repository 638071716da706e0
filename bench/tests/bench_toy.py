"""Helpers of the benchmark's CPU tests: a temporary checkout that holds
the benchmark and, added as new files, a toy cell of the dense family."""

import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TOY = pathlib.Path(__file__).resolve().parent / "data" / "toy"
TOY_CELL = "toy.backlog"


def add_toy(root: pathlib.Path) -> None:
    """Add the toy configuration, mix and metric to the checkout at
    ``root`` as new files, and name them in its BENCHMARK.json."""
    for sub in ("configs", "traffic", "metrics"):
        for f in (TOY / sub).iterdir():
            shutil.copy(f, root / "bench" / sub / f.name)
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["workloads"].append({
        "name": TOY_CELL, "config": "toy", "traffic": "toy-backlog",
        "chips": 1, "why": "CPU tests",
    })
    bench["end_to_end"].append({
        "name": "toy_finished_per_s", "unit": "1/s", "better": "higher",
        "bound": 0.25, "source": "host_clock", "workloads": [TOY_CELL],
    })
    path.write_text(json.dumps(bench))


def make_checkout(root: pathlib.Path) -> pathlib.Path:
    """A copy of the benchmark at ``root`` with the toy cell added."""
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(
        REPO / "bench", root / "bench",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    add_toy(root)
    return root


def run_toy(root, seed, **kw):
    """One toy run on the CPU through the harness, not the command line."""
    import time

    from bench import harness

    return harness.run(
        root, TOY_CELL, seed, kw.pop("seconds", 1.0), False,
        t_start=time.perf_counter(), require_tpu=False, **kw,
    )
