"""A rehearsal of the harness on the CPU: a toy cell, added as new files
in a copy of the benchmark, driven for a second through the window code
(not the command line)."""

import json

from bench import harness
from bench_toy import TOY_CELL, run_toy


def test_toy_cell_found_by_name_and_run(toy_root):
    """The toy cell, added as new files, is found by name and runs; the
    program passes and the control, read on the same sample and judged
    by the same limits, does not.

    The toy computes in float32, so its control is the reference in
    bfloat16.  Its limit (``max_logit_gap`` 0.001, in
    ``data/toy/configs/toy.json``) sits between the program's widest gap
    on fourteen seeds (0.0 on every one: the CPU computes both sides in
    float32) and the control's (0.013 at least, on about 390 served tokens
    a run)."""
    result = run_toy(toy_root, 2**33 + 7, control=True)
    assert list(result)[-1] == "checks"
    assert result["correct"] is True
    gap = result["checks"]["max_logit_gap"]
    assert gap["value"] < gap["limit"]
    assert result["compared"]["control_max_logit_gap"] > gap["limit"]
    # the control, judged by the same limits, comes out not correct
    assert result["control"]["correct"] is False
    assert result["control"]["checks"]["max_logit_gap"] == {
        "value": result["compared"]["control_max_logit_gap"],
        "limit": gap["limit"],
    }
    assert result["failed"] == 0 and result["attempted"] > 0
    m = result["metrics"]
    # the toy metric, found by its name, sits beside the cell's own
    assert set(m) == {"output_tok_s", "itl_p95_ms", "setup_s",
                      "toy_finished_per_s"}
    assert m["output_tok_s"]["unit"] == "tokens/s"
    assert m["output_tok_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert m["toy_finished_per_s"]["value"] > 0
    checks = result["checks"]
    assert checks["window_compiles"]["value"] == 0
    assert checks["tokens_compared"]["value"] >= checks["tokens_compared"]["limit"]
    assert result["device"]["platform"] == "cpu"
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}


def test_metric_selection(toy_root):
    bench, cell, _, _ = harness.load_cell(toy_root, "granite-3-8b.rag")
    e2e = {m["name"] for m in harness.metrics_for(bench, cell, False)}
    assert e2e == {"output_tok_s", "itl_p95_ms", "ttft_p95_ms", "setup_s"}
    layer = {m["name"] for m in harness.metrics_for(bench, cell, True)}
    assert {"queue_wait_p95_ms", "prefill_mfu_pct", "decode_roofline"} <= layer
    bench, cell, _, _ = harness.load_cell(toy_root, "stablelm-1.6b.chat-backlog")
    layer = {m["name"] for m in harness.metrics_for(bench, cell, True)}
    assert "queue_wait_p95_ms" not in layer and "decode_mfu_pct" in layer
    bench, cell, _, _ = harness.load_cell(toy_root, TOY_CELL)
    assert harness.metrics_for(bench, cell, True) == []


def test_warm_lengths_cover_every_bucket(toy_root):
    _, _, config, mix = harness.load_cell(toy_root, "stablelm-1.6b.chat-backlog")
    lengths = harness.warm_lengths(config, mix)
    assert lengths[0] == 128 and lengths[-1] == 3072
    assert lengths == list(range(128, 3073, 128))
    _, _, config, mix = harness.load_cell(toy_root, "granite-3-8b.rag")
    lengths = harness.warm_lengths(config, mix)
    assert lengths[0] == 256 and lengths[-1] == 3840


def test_refuses_without_a_chip(toy_root, capsys):
    """The measurement path checks for a TPU before it builds anything."""
    import time

    import pytest

    with pytest.raises(harness.NoChip):
        harness.run(toy_root, TOY_CELL, 1, 1.0, False,
                    t_start=time.perf_counter())


def test_command_line_prints_no_result_without_a_chip(toy_root):
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", TOY_CELL, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=toy_root, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "needs 1 TPU chip" in out.stderr
    json.loads((toy_root / "BENCHMARK.json").read_text())
