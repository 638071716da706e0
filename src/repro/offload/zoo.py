"""Zoo-wide offload planning: one verified plan per (arch, shape) cell.

``launch/serve.py`` / ``launch/train.py`` only *load* plans; this module is
the verification-environment side that produces them for the whole model
zoo.  For every requested (arch, kind) cell it builds the *real* step —
train / prefill / decode, the same builders production jits — wraps it in a
``BindingSpace`` over the function blocks that step exercises, runs a full
``OffloadSession`` lifecycle, and commits the winning plan to the store
under ``zoo:<arch>:<kind>``.  This is the BindingSpace analogue of what
``launch/dryrun.py`` does for compile stats.

  PYTHONPATH=src python -m repro.offload.zoo --plan-dir results/plans \\
      --arch llama3.2-1b --kind train --reduced

On a CPU container the Pallas shelf is typically not usable; the CLI
defaults to ``--targets ref,xla`` (include ``pallas`` on TPU hosts).
"""

from __future__ import annotations

import argparse
import dataclasses
import warnings
from typing import Any, Mapping, Sequence

from repro.core.planner import (
    BindingSpace,
    Objective,
    PlanStore,
    SearchStrategy,
)
from repro.offload.session import OffloadResult, OffloadSession

#: Shelf blocks each layer kind routes compute through (see repro.models).
_BLOCKS_BY_LAYER_KIND = {
    "a": ("rmsnorm", "attention"),
    "d": ("rmsnorm", "attention"),
    "s": ("rmsnorm", "attention"),
    "m": ("rmsnorm", "ssd_scan"),
}

#: Extra blocks the *decode* cell exercises per layer kind: decode cells
#: trace through the paged KV pool (the serving layout), so the hot-loop
#: attention read is the planner-searchable paged_attention block.
_DECODE_BLOCKS_BY_LAYER_KIND = {
    "a": ("paged_attention",),
    "d": ("paged_attention",),
    "s": ("paged_attention",),
}

ZOO_KINDS = ("train", "prefill", "decode")


def canonical_arch(arch: str) -> str:
    """Registry spelling of an arch name (``llama3.2_1b`` ->
    ``llama3.2-1b``); unknown names pass through unchanged so non-zoo
    callers (e.g. the report selftest) can use arbitrary labels."""
    try:
        from repro.configs import get_config

        return get_config(arch).name
    except Exception:  # noqa: BLE001 — unknown arch: keep caller's label
        return arch


def zoo_key(arch: str, kind: str) -> str:
    # canonicalised so every spelling a driver accepts (get_config is
    # permissive) addresses the same stored plan
    return f"zoo:{canonical_arch(arch)}:{kind}"


def default_plan_key(
    plan_dir: str | None,
    arch: str,
    kind: str,
    match_fingerprint: bool = False,
) -> str | None:
    """``zoo:<arch>:<kind>`` when the store actually holds that plan, else
    None — lets launch drivers default ``--plan-key`` without emitting
    "plan not found" noise on hosts that never ran the zoo sweep.

    By default presence only (fingerprint/registry compatibility is still
    enforced at bind time by ``OffloadSession.attach``).  Pass
    ``match_fingerprint=True`` when deciding whether a *search* is needed:
    a plan verified under a different environment would be rejected at
    bind time, so for search purposes it counts as missing.
    """
    if not plan_dir:
        return None
    key = zoo_key(arch, kind)
    plan = PlanStore(plan_dir).load(key, match_fingerprint=match_fingerprint)
    return None if plan is None else key


def launch_plan_keys(
    plan_dir: str | None,
    arch: str,
    kinds: Sequence[str],
    *,
    search: bool = False,
    targets: Sequence[str] | None = None,
    executor: Any = None,
    meter: Any = None,
) -> dict[str, str | None]:
    """The launch drivers' zoo-default flow, in one place: optionally
    search+commit any cell whose stored plan is absent **or verified under
    a different environment** (it would be rejected at bind time, so for
    search purposes it counts as missing), then return each kind's
    bindable default key (presence-checked; attach still enforces
    compatibility)."""
    if not plan_dir:
        return {kind: None for kind in kinds}
    if search:
        missing = [
            kind
            for kind in kinds
            if default_plan_key(plan_dir, arch, kind, match_fingerprint=True)
            is None
        ]
        if missing:
            print(f"searching offload plans for {arch}: {missing}")
            plan_zoo(
                plan_dir,
                [(arch, kind) for kind in missing],
                targets=targets,
                executor=executor,
                meter=meter,
                quiet=False,
            )
    return {
        kind: default_plan_key(plan_dir, arch, kind) for kind in kinds
    }


def _cell_blocks(
    cfg: Any,
    registry: Any,
    targets: Sequence[str] | None,
    kind: str = "train",
) -> dict[str, list[str]]:
    """Axes for one cell: the blocks this arch's step actually exercises,
    restricted to the requested (and registered) targets."""
    wanted: list[str] = []
    per_kind = dict(_BLOCKS_BY_LAYER_KIND)
    if kind == "decode":
        per_kind = {
            k: v + _DECODE_BLOCKS_BY_LAYER_KIND.get(k, ())
            for k, v in per_kind.items()
        }
    for kind_char in dict.fromkeys(cfg.pattern()):
        for b in per_kind.get(kind_char, ()):
            if b not in wanted:
                wanted.append(b)
    out: dict[str, list[str]] = {}
    for b in wanted:
        avail = registry.targets(b)
        chosen = [t for t in (targets or avail) if t in avail]
        if len(chosen) > 1:
            out[b] = chosen
    return out


def _materialize(spec: Mapping[str, Any], cfg: Any, rng: Any):
    """Concrete jnp inputs for a tree of ShapeDtypeStructs."""
    import jax.numpy as jnp

    out = {}
    for k, s in spec.items():
        if jnp.issubdtype(s.dtype, jnp.integer):
            out[k] = jnp.asarray(
                rng.integers(0, cfg.vocab_size, s.shape), s.dtype
            )
        else:
            out[k] = jnp.asarray(rng.standard_normal(s.shape), s.dtype)
    return out


def _cell_target(
    arch: str,
    kind: str,
    *,
    reduced: bool,
    layers: int,
    batch: int,
    seq: int,
    seed: int,
):
    """(step_builder, args, cfg) for one zoo cell, using the production
    step builders from ``launch/steps``."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.launch.steps import TrainHyper, input_specs, make_train_step
    from repro.models import lm
    from repro.optim.adamw import AdamW

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    if layers:
        cfg = dataclasses.replace(
            cfg,
            n_layers=layers,
            block_pattern=None if cfg.block_pattern is None
            else cfg.pattern()[:layers],
        )
    shape = ShapeConfig(f"zoo_{kind}", seq, batch, kind)  # type: ignore[arg-type]
    rng = np.random.default_rng(seed)
    params = lm.init_params(cfg, seed=seed)
    batch_tree = _materialize(input_specs(cfg, shape), cfg, rng)

    if kind == "train":
        opt = AdamW(moment_dtype=cfg.opt_dtype)
        step = make_train_step(
            cfg, opt, TrainHyper(warmup_steps=2, total_steps=16)
        )

        def builder():
            return jax.jit(step)

        args = (params, opt.init(params), batch_tree)
    elif kind == "prefill":
        def builder():
            return jax.jit(lambda p, b, c: lm.prefill(p, b, cfg, c))

        args = (params, batch_tree, lm.init_cache(cfg, batch, seq))
    elif kind == "decode":
        def builder():
            return jax.jit(lambda p, t, c: lm.decode_step(p, t, cfg, c)[:2])

        # attention-family decode traces through the block-paged KV pool
        # (the serving layout), so the cell's binding space includes the
        # paged_attention hot-loop block; pure-SSM archs have no sequence
        # axis to page and keep the contiguous state
        if any(ch in "ads" for ch in cfg.pattern()):
            import jax.numpy as jnp

            page_size = max(1, min(8, seq))
            max_pages = -(-seq // page_size)
            cache = lm.init_cache(
                cfg, batch, seq,
                page_size=page_size, n_pages=batch * max_pages,
            )
            # identity table: slot b owns pages [b*mp, (b+1)*mp); ragged
            # per-slot positions so the cell measures the staggered
            # continuous-batching case, not the aligned one
            cache = dict(
                cache,
                pages=jnp.arange(
                    batch * max_pages, dtype=jnp.int32
                ).reshape(batch, max_pages),
                index=jnp.arange(batch, dtype=jnp.int32) % jnp.int32(seq),
            )
        else:
            cache = lm.init_cache(cfg, batch, seq)
        args = (params, batch_tree["tokens"], cache)
    else:
        raise ValueError(f"unknown cell kind '{kind}'; known: {ZOO_KINDS}")
    return builder, args, cfg


def plan_zoo(
    store: PlanStore | str,
    cells: Sequence[tuple[str, str]] | None = None,
    *,
    reduced: bool = True,
    layers: int = 2,
    batch: int = 2,
    seq: int = 16,
    targets: Sequence[str] | None = None,
    objective: Objective | str | None = None,
    strategy: SearchStrategy | None = None,
    executor: Any = None,
    meter: Any = None,
    repeats: int = 1,
    min_seconds: float = 0.0,
    registry: Any = None,
    seed: int = 0,
    verify: bool = False,
    force_search: bool = False,
    legality: bool = False,
    resources: Any = False,
    quiet: bool = True,
) -> dict[tuple[str, str], OffloadResult]:
    """Search and persist an offload plan for every (arch, kind) cell.

    ``cells`` defaults to every registered architecture x every step kind.
    Already-stored compatible plans short-cut to zero measurements (pass
    ``force_search=True`` to re-measure).  ``executor`` / ``meter`` select
    the ``repro.metering`` measurement executor (e.g. ``device_parallel``
    on multi-device hosts) and power meter (``"auto"`` autodetects, with
    provenance recorded on every trial).  ``legality=True`` runs the
    ``repro.analysis`` static legality pass per cell so strategies prune
    statically-illegal bindings instead of measuring them (required when
    ``targets`` includes 'pallas' on a non-TPU host).  ``resources``
    (True / "host" / an envelope name / a ``DeviceEnvelope``) additionally
    runs the memory-envelope pass so statically-OOM bindings are pruned
    before measurement — the paper's FPGA resource-fit check.  Returns
    ``{(arch, kind): OffloadResult}``; cells whose step cannot be built or
    measured on this host are skipped with a ``UserWarning`` (regardless
    of ``quiet``, which only silences progress lines) rather than
    aborting the sweep.
    """
    from repro.configs import ARCH_NAMES
    from repro.core import blocks as blocks_mod
    from repro.metering import resolve_meter

    registry = registry or blocks_mod.registry
    store = PlanStore(store) if isinstance(store, str) else store
    meter = resolve_meter(meter)
    if cells is None:
        cells = [(a, k) for a in ARCH_NAMES for k in ZOO_KINDS]

    results: dict[tuple[str, str], OffloadResult] = {}
    for arch, kind in cells:
        try:
            builder, args, cfg = _cell_target(
                arch, kind, reduced=reduced, layers=layers, batch=batch,
                seq=seq, seed=seed,
            )
            block_map = _cell_blocks(cfg, registry, targets, kind)
            if not block_map:
                if not quiet:
                    print(f"zoo cell {arch}:{kind}: no searchable blocks "
                          f"for targets={targets}; skipped")
                continue
            space = BindingSpace(
                builder,
                blocks=block_map,
                registry=registry,
                tag=f"zoo:{arch}:{kind}:b{batch}xs{seq}",
            )
            session = OffloadSession(
                space,
                args=args,
                objective=objective,
                strategy=strategy,
                store=store,
                key=zoo_key(arch, kind),
                meter=meter,
                executor=executor,
                repeats=repeats,
                min_seconds=min_seconds,
                registry=registry,
                force_search=force_search,
                legality=legality,
                resources=resources,
            )
            result = session.run(verify=verify)
        except Exception as e:  # noqa: BLE001 — keep sweeping other cells
            warnings.warn(
                f"zoo cell {arch}:{kind} failed: {type(e).__name__}: {e}",
                stacklevel=2,
            )
            continue
        results[(arch, kind)] = result
        if not quiet:
            src = "store" if result.from_store else result.plan.strategy
            pruned = getattr(result.report, "pruned", 0) if result.report else 0
            pruned_note = f" pruned={pruned}" if pruned else ""
            print(
                f"zoo cell {arch}:{kind}: {result.mapping or '(baseline)'} "
                f"speedup={result.speedup:.2f}x via {src} "
                f"[{result.objective}]{pruned_note}"
            )
    return results


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--plan-dir", required=True,
                    help="PlanStore directory to commit plans into")
    ap.add_argument("--arch", default="all",
                    help="comma-separated arch names, or 'all'")
    ap.add_argument("--kind", default="all",
                    help="comma-separated step kinds (train,prefill,decode)")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="search reduced configs (--no-reduced for full "
                         "production configs on real hardware)")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--targets", default="ref,xla",
                    help="comma-separated targets to search over "
                         "(add 'pallas' on TPU hosts)")
    ap.add_argument("--legality", action="store_true",
                    help="run the repro.analysis static legality pass per "
                         "cell; statically-illegal bindings are pruned "
                         "from the search instead of measured")
    ap.add_argument("--resources", action="store_true",
                    help="run the repro.analysis memory-envelope pass per "
                         "cell; statically-OOM bindings are pruned from "
                         "the search instead of measured")
    ap.add_argument("--envelope", default=None,
                    help="device envelope for --resources: a static name "
                         "(e.g. a100-40g, cpu-host-16g, tiny-32m) or "
                         "'host' to probe the live device (default)")
    ap.add_argument("--objective", default="latency",
                    help="latency | perf_per_watt")
    ap.add_argument("--executor", default="serial",
                    help="measurement executor: serial | device-parallel "
                         "| batched (repro.metering)")
    ap.add_argument("--meter", default="none",
                    help="power meter: none | auto | time | nvml | rapl | "
                         "psutil (provenance recorded per trial)")
    ap.add_argument("--repeats", type=int, default=1)
    ap.add_argument("--force", action="store_true",
                    help="re-search even when a stored plan exists")
    ap.add_argument("--verify", action="store_true",
                    help="run the numerics stage per cell")
    args = ap.parse_args()

    from repro.configs import ARCH_NAMES

    archs = ARCH_NAMES if args.arch == "all" else args.arch.split(",")
    kinds = ZOO_KINDS if args.kind == "all" else tuple(args.kind.split(","))
    cells = [(a, k) for a in archs for k in kinds]
    results = plan_zoo(
        args.plan_dir,
        cells,
        reduced=args.reduced,
        layers=args.layers,
        batch=args.batch,
        seq=args.seq,
        targets=tuple(args.targets.split(",")),
        objective=args.objective,
        executor=args.executor,
        meter=args.meter,
        repeats=args.repeats,
        verify=args.verify,
        force_search=args.force,
        legality=args.legality,
        resources=(args.envelope or True) if args.resources else False,
        quiet=False,
    )
    print(f"planned {len(results)}/{len(cells)} cells -> {args.plan_dir}")


if __name__ == "__main__":
    main()
