"""Paged-attention microbenchmark: fused page walk vs the XLA block walk.

Sweeps page sizes at a fixed decode shape and reports, per
``(page_size, impl)`` cell, the measured step latency and the static
memory envelope (``repro.analysis.resources.estimate_memory``) of a
jitted single-block decode call.  The XLA walk is always timed
on the local backend; the fused Pallas kernel is timed only where it can
actually run — on a TPU, or in interpret mode when ``--interpret`` is
passed (orders of magnitude slower; parity checking only, not a
performance number).  The static estimates are platform-independent, so
the peak-live-bytes comparison the planner's resource pass relies on is
recorded even on CPU-only hosts.

  PYTHONPATH=src python benchmarks/paged_attention_bench.py \
      --json-out BENCH_paged_attn.json

``make bench-paged-attn`` runs the CI-sized sweep.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "src")
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from common import emit, emit_header, time_call  # noqa: E402
from repro.analysis.resources import estimate_memory  # noqa: E402
from repro.kernels import ops  # noqa: E402


def git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
    except Exception:  # noqa: BLE001 — the snapshot is still useful
        return "unknown"


def make_operands(rng, *, batch, heads, kv_heads, head_dim, seq, page_size):
    """Ragged decode operands: per-slot lengths spread across [1, seq]."""
    max_pages = -(-seq // page_size)
    n_pages = batch * max_pages
    # one layer of the stacked token-major pools
    pool_shape = (1, n_pages + 1, page_size, kv_heads * head_dim)
    k_pool = jnp.asarray(rng.standard_normal(pool_shape), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal(pool_shape), jnp.float32)
    q = jnp.asarray(
        rng.standard_normal((batch, heads, 1, head_dim)), jnp.float32
    )
    lengths = np.linspace(1, seq - 1, batch).astype(np.int32)
    pages = np.arange(n_pages, dtype=np.int32).reshape(batch, max_pages)
    for i, ln in enumerate(lengths):
        pages[i, -(-(int(ln) + 1) // page_size):] = n_pages  # null page
    return q, k_pool, v_pool, jnp.asarray(pages), jnp.asarray(lengths)


def bench_cell(args, page_size, backend, interpret):
    rng = np.random.default_rng(args.seed)
    operands = make_operands(
        rng, batch=args.batch, heads=args.heads, kv_heads=args.kv_heads,
        head_dim=args.head_dim, seq=args.seq, page_size=page_size,
    )

    def step(q, k_pool, v_pool, pages, index):
        return ops.paged_attention(
            q, k_pool, v_pool, pages, index, 0,
            backend=backend, interpret=interpret or None,
        )

    est = estimate_memory(step, *operands)
    on_tpu = jax.default_backend() == "tpu"
    timed = backend == "xla" or on_tpu or interpret
    seconds = (
        time_call(jax.jit(step), operands, repeats=args.repeats)
        if timed else None
    )
    return {
        "page_size": page_size,
        "impl": backend,
        "interpret": bool(interpret) and not on_tpu,
        "seconds": seconds,
        "tokens_per_second": (
            args.batch / seconds if seconds else None
        ),
        "peak_live_bytes": est.peak_live_bytes,
        "operand_bytes": est.operand_bytes,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--seq", type=int, default=512,
                    help="pool capacity per slot (max context)")
    ap.add_argument("--page-sizes", type=int, nargs="+",
                    default=[8, 16, 32, 64])
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true",
                    help="time the Pallas kernel in interpret mode off-TPU "
                         "(slow; parity path, not a performance number)")
    ap.add_argument("--json-out", default=None,
                    help="write a machine-readable snapshot "
                         "(e.g. BENCH_paged_attn.json)")
    args = ap.parse_args()

    emit_header()
    cells = []
    for ps in args.page_sizes:
        for backend in ("xla", "pallas"):
            cell = bench_cell(args, ps, backend, args.interpret)
            cells.append(cell)
            peak = f"peak={cell['peak_live_bytes']}B"
            if cell["seconds"] is not None:
                emit(f"paged_attn/{backend}/ps{ps}", cell["seconds"], peak)
            else:
                print(f"paged_attn/{backend}/ps{ps},untimed "
                      f"(TPU-only kernel),{peak}", flush=True)

    if args.json_out:
        record = {
            "schema": 1,
            "benchmark": "paged_attention",
            "git_sha": git_sha(),
            "timestamp": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
            ),
            "backend": jax.default_backend(),
            "shape": {
                "batch": args.batch,
                "heads": args.heads,
                "kv_heads": args.kv_heads,
                "head_dim": args.head_dim,
                "seq": args.seq,
            },
            "repeats": args.repeats,
            "cells": cells,
        }
        with open(args.json_out, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        print(f"snapshot written: {args.json_out}")


if __name__ == "__main__":
    main()
