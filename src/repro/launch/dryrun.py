import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell the *production* step function (train / prefill / decode) is
lowered with ShapeDtypeStruct inputs under the production mesh and compiled;
we record:
  * memory_analysis()  — per-device bytes (proves the cell fits 16 GB HBM),
  * cost_analysis()    — per-device HLO FLOPs / bytes accessed,
  * collective bytes   — parsed from the compiled HLO (all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute),
  * lower/compile wall time.
Results land in a JSON file that benchmarks/roofline.py turns into the
EXPERIMENTS.md §Roofline table.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod both --out results/dryrun.json
"""

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config, get_shape, ARCH_NAMES, SHAPES  # noqa: E402
from repro.launch.mesh import HW, make_production_mesh, mesh_shape_dict  # noqa: E402
from repro.launch import hlo_cost, steps  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.models import params as pm  # noqa: E402
from repro.optim.adamw import AdamW  # noqa: E402
from repro.sharding.specs import rules_for  # noqa: E402
from repro.sharding.utils import resolve_spec, use_sharding  # noqa: E402

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8\w*|s64|s32|s16|s8|u64|u32|u16|u8|pred|c64|c128)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt[:4].rstrip("["), _DTYPE_BYTES.get(dt, 4))
    return total


def parse_collectives(hlo_text: str) -> dict[str, int]:
    """Per-device bytes per collective kind, from post-SPMD HLO."""
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    counts: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+(\S+)\(", ls)
        if not m:
            continue
        result_type, opname = m.groups()
        for kind in _COLLECTIVES:
            if opname == kind or opname.startswith(kind + "-start") or opname.startswith(kind + "."):
                out[kind] += _shape_bytes(result_type)
                counts[kind] += 1
                break
    out_counts = {f"n_{k}": v for k, v in counts.items()}
    out.update(out_counts)  # type: ignore[arg-type]
    return out


def _named(tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree)


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides: dict | None = None):
    """Build (jitted_fn, abstract_args) for one cell.

    ``overrides`` (perf-iteration knobs):
      param_dtype / opt_dtype / compute_dtype: str
      microbatch: int           grad-accumulation chunks (train)
      ep_mode: "gather"|"psum"  MoE expert-weight strategy
      scores_dtype: "float32"|"bfloat16"  attention score blocks
      remat: "full"|"none"
    """
    import dataclasses as _dc

    from repro.kernels import attention_xla as _attn_xla

    ov = dict(overrides or {})
    cfg = get_config(arch)
    cfg_fields = {
        k: ov.pop(k)
        for k in ("param_dtype", "opt_dtype", "compute_dtype", "remat",
                  "n_heads")
        if k in ov
    }
    if cfg_fields:
        cfg = _dc.replace(cfg, **cfg_fields)
    # the knob lives on the module that reads it (the chunked kernel moved
    # to the shelf), mirroring _kref.RMSNORM_PRECISION below
    _attn_xla.CHUNKED_SCORES_DTYPE = ov.pop("scores_dtype", "float32")
    from repro.kernels import ref as _kref
    _kref.RMSNORM_PRECISION = ov.pop("norm_precision", "full")
    from repro.models import layers as _lay
    _lay.BF16_TP_REDUCE = ov.pop("bf16_tp_reduce", False)
    _lay.MEGATRON_MLP = ov.pop("megatron_mlp", False)
    from repro.models import lm as _lm
    _lm.REMAT_POLICY = ov.pop("remat_policy", "none")
    microbatch = ov.pop("microbatch", 2)
    ep_mode = ov.pop("ep_mode", "gather")
    if ov:
        raise ValueError(f"unknown overrides: {sorted(ov)}")
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    msd = mesh_shape_dict(mesh)
    rules = rules_for(cfg, shape, msd, ep_mode=ep_mode)

    metas = lm.build_metas(cfg)
    params_abs = pm.abstract_params(metas)
    pspec = pm.spec_tree(metas, rules)
    pshard = _named(pspec, mesh)

    batch_axes = rules.get("act_batch")
    bspec_tok = P(batch_axes, None)
    bspec_emb = P(batch_axes, None, None)

    def batch_shardings(b_abs):
        return {
            k: NamedSharding(mesh, bspec_emb if v.ndim == 3 else bspec_tok)
            for k, v in b_abs.items()
        }

    ctx = use_sharding(mesh, rules)

    if shape.kind == "train":
        opt = AdamW(moment_dtype=cfg.opt_dtype)
        params_abs, opt_abs = steps.abstract_state(cfg, opt)
        oshard = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            type(opt_abs)(
                mu=pspec, nu=pspec,
                step=P(),
            ),
        )
        batch_abs = steps.input_specs(cfg, shape)
        bshard = batch_shardings(batch_abs)
        # baseline microbatching: 2 grad-accumulation chunks halve the
        # per-layer residual stacks (the dominant train-memory term)
        fn = steps.make_train_step(
            cfg, opt, steps.TrainHyper(microbatch=microbatch),
            grad_shardings=pshard,
        )
        jitted = jax.jit(
            fn,
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, None),
            donate_argnums=(0, 1),
        )
        args = (params_abs, opt_abs, batch_abs)
    elif shape.kind == "prefill":
        batch_abs = steps.input_specs(cfg, shape)
        bshard = batch_shardings(batch_abs)
        cache_metas = lm.cache_metas_tree(cfg, shape.global_batch, shape.seq_len)
        cshard = _named(pm.spec_tree(cache_metas, rules), mesh)
        fn = steps.make_prefill_step(cfg, shape)
        jitted = jax.jit(
            fn,
            in_shardings=(pshard, bshard),
            out_shardings=(None, cshard),
        )
        args = (params_abs, batch_abs)
    else:  # decode
        batch_abs = steps.input_specs(cfg, shape)
        bshard = batch_shardings(batch_abs)
        cache_metas = lm.cache_metas_tree(cfg, shape.global_batch, shape.seq_len)
        cache_abs = steps.abstract_cache(cfg, shape)
        cspec = pm.spec_tree(cache_metas, rules)
        cshard = _named(cspec, mesh)
        fn = steps.make_decode_step(cfg)
        jitted = jax.jit(
            fn,
            in_shardings=(pshard, cshard, bshard),
            out_shardings=(None, cshard),
            donate_argnums=(1,),
        )
        args = (params_abs, cache_abs, batch_abs)

    return cfg, shape, mesh, ctx, jitted, args


def model_flops(cfg, shape) -> float:
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6 if shape.kind == "train" else 2
    return float(mult * n_active * tokens)


def _save_hlo(txt: str, arch: str, shape_name: str, mesh: str,
              hlo_dir: str) -> None:
    import zstandard

    d = pathlib.Path(hlo_dir)
    d.mkdir(parents=True, exist_ok=True)
    name = f"{arch}_{shape_name}_{mesh}.hlo.zst"
    (d / name).write_bytes(zstandard.compress(txt.encode()))


def load_hlo(arch: str, shape_name: str, mesh: str,
             hlo_dir: str = "results/hlo") -> str | None:
    import zstandard

    p = pathlib.Path(hlo_dir) / f"{arch}_{shape_name}_{mesh}.hlo.zst"
    if not p.exists():
        return None
    return zstandard.decompress(p.read_bytes()).decode()


def reparse(out_path: str, hlo_dir: str = "results/hlo") -> None:
    """Recompute the cost-model fields of an existing results JSON from the
    saved HLO texts (no recompilation)."""
    path = pathlib.Path(out_path)
    results = json.loads(path.read_text())
    for rec in results:
        if rec.get("status") != "ok":
            continue
        txt = load_hlo(rec["arch"], rec["shape"], rec["mesh"], hlo_dir)
        if txt is None:
            continue
        parsed = hlo_cost.analyze(txt)
        rec["hlo_flops_per_device"] = parsed["flops"]
        rec["hlo_bytes_per_device"] = parsed["hbm_bytes"]
        rec["collectives_per_device"] = {
            k: float(v) for k, v in parsed["collectives"].items()
        }
        rec["collective_bytes_per_device"] = parsed["collective_bytes"]
        print(f"reparsed {rec['arch']} x {rec['shape']} x {rec['mesh']}: "
              f"flops/dev={parsed['flops']:.3g}", flush=True)
    path.write_text(json.dumps(results, indent=1))


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             hlo_dir: str | None = None, overrides: dict | None = None) -> dict:
    rec: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
    }
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    if shape.name == "long_500k" and not cfg.subquadratic:
        rec["status"] = "skipped"
        rec["reason"] = "full-attention arch at 500k context (see DESIGN.md)"
        return rec
    if overrides:
        rec["overrides"] = dict(overrides)
    try:
        cfg, shape, mesh, ctx, jitted, args = build_cell(
            arch, shape_name, multi_pod, overrides
        )
        chips = mesh.devices.size
        t0 = time.perf_counter()
        with ctx:
            lowered = jitted.lower(*args)
        rec["lower_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t0, 2)

        mem = compiled.memory_analysis()
        for attr in (
            "temp_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            v = getattr(mem, attr, None)
            if v is not None:
                rec[attr] = int(v)
        cost = compiled.cost_analysis() or {}
        rec["xla_flops_per_device"] = float(cost.get("flops", 0.0))
        rec["xla_bytes_per_device"] = float(cost.get("bytes accessed", 0.0))
        txt = compiled.as_text()
        if hlo_dir:
            _save_hlo(txt, arch, shape_name, rec["mesh"], hlo_dir)
        # loop-aware cost model (XLA's cost_analysis counts while bodies once)
        parsed = hlo_cost.analyze(txt)
        rec["hlo_flops_per_device"] = parsed["flops"]
        rec["hlo_bytes_per_device"] = parsed["hbm_bytes"]
        rec["collectives_per_device"] = {
            k: float(v) for k, v in parsed["collectives"].items()
        }
        rec["collective_bytes_per_device"] = parsed["collective_bytes"]
        rec["chips"] = chips
        rec["model_flops"] = model_flops(cfg, shape)
        # peak HBM need per device: arguments (params+opt+cache stay resident)
        # + temporaries.  Donated args alias outputs.
        args_b = rec.get("argument_size_in_bytes", 0)
        temp_b = rec.get("temp_size_in_bytes", 0)
        out_b = rec.get("output_size_in_bytes", 0)
        alias_b = rec.get("alias_size_in_bytes", 0)
        rec["peak_bytes_per_device"] = args_b + temp_b + max(out_b - alias_b, 0)
        rec["fits_16gb"] = rec["peak_bytes_per_device"] < 16e9
    except Exception as e:  # noqa: BLE001
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument(
        "--multi-pod", choices=("single", "multi", "both"), default="both"
    )
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--append", action="store_true")
    ap.add_argument("--save-hlo", default=None,
                    help="directory to save compiled HLO text (zstd)")
    ap.add_argument("--reparse", action="store_true",
                    help="recompute costs from saved HLO, no compilation")
    args = ap.parse_args()

    if args.reparse:
        reparse(args.out, args.save_hlo or "results/hlo")
        return

    cells: list[tuple[str, str]] = []
    archs = ARCH_NAMES if (args.all or args.arch is None) else (args.arch,)
    shapes = tuple(SHAPES) if (args.all or args.shape is None) else (args.shape,)
    for a in archs:
        for s in shapes:
            cells.append((a, s))

    pods = {"single": (False,), "multi": (True,), "both": (False, True)}[
        args.multi_pod
    ]
    out_path = pathlib.Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results: list[dict] = []
    if args.append and out_path.exists():
        results = json.loads(out_path.read_text())
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}

    for a, s in cells:
        for mp in pods:
            key = (a, s, "2x16x16" if mp else "16x16")
            if key in done:
                continue
            t0 = time.perf_counter()
            rec = run_cell(a, s, mp, hlo_dir=args.save_hlo)
            dt = time.perf_counter() - t0
            status = rec["status"]
            extra = ""
            if status == "ok":
                extra = (
                    f" peak={rec['peak_bytes_per_device']/1e9:.2f}GB"
                    f" flops/dev={rec['hlo_flops_per_device']:.3g}"
                )
            elif status == "error":
                extra = " " + rec["error"][:120]
            print(f"[{dt:7.1f}s] {a} x {s} x {rec['mesh']}: {status}{extra}",
                  flush=True)
            results.append(rec)
            out_path.write_text(json.dumps(results, indent=1))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_err} errors -> {out_path}")


if __name__ == "__main__":
    main()
