#!/usr/bin/env python3
"""Smoke run of the serving engine and the paper's offload path on one TPU.

    python3 chip_smoke.py

Everything runs in this one process, which holds the chip; it starts no
child process.  The phases, in order:

  a. serving at full width: ``ServeEngine`` on llama3.2-1b (16 layers,
     d_model 2048, vocab 128256, random weights from a seed) with the paged
     KV cache serves 8 greedy requests twice on the same parameters, once
     with ``decode_impl="xla"`` and once with ``"pallas"``.  Every request
     must complete; the pallas decode program must hold the fused Mosaic
     kernel; each engine's tokens must match a teacher-forced full forward
     pass wherever the reference's top-two margin decides the argmax.
  b. the paper's offload path: ``OffloadSession.run()`` on the FFT and LU
     applications at n = 192 (not a multiple of the 128 tile) and 256.
     Each must commit the offloaded pattern with its numerics verified,
     and the wrapper it binds must compile to a Mosaic kernel.
  c. the device envelope: ``probe_device_envelope()`` must read the chip's
     allocator limit, not fall back to host RAM.

A failed check raises, so the script exits non-zero.  On a host whose JAX
finds no TPU it exits 1 before running anything.  Its last line on success
is one JSON object naming the device, as JAX reports it.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

#: a served token must equal the reference argmax wherever the reference's
#: top-two logit margin exceeds this share of the top logit's magnitude.
#: Logits are bfloat16 (8 significant bits, one rounding = 1/256); the
#: cached decode path and the full forward pass order their bf16 work
#: differently across 16 layers, so near-ties may flip.  1/16 leaves room
#: for sixteen roundings.
MARGIN_REL = 1.0 / 16
#: absolute floor of the margin tolerance, for logits near zero
MARGIN_ABS = 1e-2
KERNEL_MARK = "tpu_custom_call"


def _log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def compiled_text(fn, *args) -> str:
    """The compiled program text of ``fn`` at ``args`` (shapes suffice)."""
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return jitted.lower(*args).compile().as_text()


def teacher_forced_check(params, cfg, prompts, generated) -> dict:
    """Reference top-two logits from ``lm.forward`` over prompt + served
    tokens, one request at a time, padded to one length (the model is
    causal, so right padding leaves earlier positions untouched).

    Returns counts of decided positions, mismatches there (must be 0), and
    flips at undecided near-ties.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm

    seqs = [list(p) + list(g) for p, g in zip(prompts, generated)]
    length = -(-max(map(len, seqs)) // 128) * 128

    @jax.jit
    def top2(params, tokens):
        logits = lm.forward(params, {"tokens": tokens}, cfg)[0]
        return jax.lax.top_k(logits[..., : cfg.vocab_size].astype(jnp.float32), 2)

    decided = mismatched = flipped = 0
    min_decided_margin = float("inf")
    for prompt, seq in zip(prompts, seqs):
        tokens = np.zeros((1, length), np.int32)
        tokens[0, : len(seq)] = seq
        vals, idx = (np.asarray(a[0]) for a in top2(params, tokens))
        for pos in range(len(prompt), len(seq)):
            top1, top2_ = vals[pos - 1]
            margin = float(top1 - top2_)
            agree = int(idx[pos - 1, 0]) == seq[pos]
            if margin > MARGIN_ABS + MARGIN_REL * abs(float(top1)):
                decided += 1
                mismatched += not agree
                min_decided_margin = min(min_decided_margin, margin)
            else:
                flipped += not agree
    return {
        "positions": sum(len(s) - len(p) for p, s in zip(prompts, seqs)),
        "decided": decided,
        "mismatched": mismatched,
        "flipped_near_ties": flipped,
        "min_decided_margin": min_decided_margin,
    }


def serve_phase(
    cfg,
    *,
    prompt_lens=(17, 23, 29, 32, 40, 47, 55, 64),
    gen: int = 32,
    n_slots: int = 8,
    max_len: int = 1024,
    page_size: int = 16,
    bucket: int = 32,
    seed: int = 0,
    require_kernel: bool = True,
) -> dict:
    """Phase a: serve the same greedy requests with the xla and pallas
    paged-attention bindings on one set of parameters and check both."""
    import numpy as np

    from repro.core import blocks
    from repro.models import lm
    from repro.serve import Request, ServeEngine

    params = lm.init_params(cfg, seed=seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in prompt_lens]
    out: dict = {"requests": len(prompts)}
    tokens: dict[str, list[list[int]]] = {}
    for impl in ("xla", "pallas"):
        engine = ServeEngine(
            cfg, params=params, n_slots=n_slots, max_len=max_len,
            page_size=page_size, prefill_bucket=bucket, decode_impl=impl,
            seed=seed,
        )
        ids = [engine.submit(Request(p, max_new_tokens=gen)) for p in prompts]
        engine.run_until_idle()
        done = [engine.completions.get(i) for i in ids]
        assert all(c is not None for c in done), f"{impl}: requests left"
        tokens[impl] = [list(c.tokens) for c in done]
        assert all(len(t) == gen for t in tokens[impl]), (
            f"{impl}: a request stopped short of {gen} tokens"
        )
        prefill = engine.programs.records["prefill"]
        decode = engine.programs.records["decode"]
        (structs,) = decode.signatures.values()
        # the engine traces decode under this binding; so does the check
        with blocks.registry.bind({"paged_attention": impl}):
            has_kernel = KERNEL_MARK in compiled_text(decode.fn, *structs)
        check = teacher_forced_check(params, cfg, prompts, tokens[impl])
        out[impl] = {
            "completed": len(done),
            "prefill_programs": len(prefill.signatures),
            "decode_kernel": has_kernel,
            **check,
        }
        _log(f"a. {impl}: {out[impl]}")
        if require_kernel:
            assert has_kernel == (impl == "pallas"), (
                f"{impl} decode program: {KERNEL_MARK} present={has_kernel}"
            )
        assert check["decided"] > 0, f"{impl}: no position was decided"
        assert check["mismatched"] == 0, f"{impl}: teacher-forced {check}"
        del engine
    same = sum(
        a == b
        for x, p in zip(tokens["xla"], tokens["pallas"])
        for a, b in zip(x, p)
    )
    out["xla_pallas_agreement"] = same / (len(prompts) * gen)
    _log(f"a. xla vs pallas token agreement: {same}/{len(prompts) * gen}")
    return out


def offload_phase(sizes=(192, 256), *, require_kernel: bool = True) -> list:
    """Phase b: the paper's lifecycle on both applications at each size."""
    import numpy as np

    from repro.apps import fourier, matrix
    from repro.kernels import ops
    from repro.offload import OffloadSession

    apps = (
        ("fft2d", fourier.fourier_app_libcall, fourier.make_input,
         lambda x: compiled_text(ops.fft2d, x.astype(np.complex64))),
        ("lu", matrix.matrix_app_libcall, matrix.make_input,
         lambda a: compiled_text(ops.lu_nr_compat, a.astype(np.float32))),
    )
    rows = []
    for n in sizes:
        for block, app, make_input, program in apps:
            x = make_input(n)
            res = OffloadSession(app, args=(x,), repeats=1).run()
            row = {
                "block": block, "n": n, "pattern": list(res.pattern),
                "numerics_ok": res.numerics_ok,
            }
            if require_kernel:
                row["kernel"] = KERNEL_MARK in program(x)
            _log(f"b. {row}")
            assert res.pattern == (block,), f"not offloaded: {row}"
            assert res.numerics_ok is True, f"verify failed: {row}"
            assert row.get("kernel", True), f"no Mosaic kernel: {row}"
            rows.append(row)
    return rows


def envelope_phase():
    """Phase c: the live device's memory envelope, from memory_stats()."""
    import jax

    from repro.analysis.devices import probe_device_envelope

    env = probe_device_envelope()
    stats = jax.devices()[0].memory_stats() or {}
    _log(f"c. envelope: {env}")
    assert env.platform == "tpu", env
    assert env.memory_bytes == int(stats.get("bytes_limit", -1)), (env, stats)
    return env


def main() -> int:
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {device.platform!r})",
              file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    _log(f"compile cache: {enable_compile_cache()}")
    for name, run in (
        ("a", lambda: serve_phase(get_config("llama3.2-1b"))),
        ("b", offload_phase),
        ("c", envelope_phase),
    ):
        t0 = time.perf_counter()
        run()
        _log(f"phase {name} passed ({time.perf_counter() - t0:.1f} s "
             "wall, compiles included)")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
