"""Training driver.

Wires together: config -> synthetic data pipeline -> jitted train step ->
fault-tolerant loop (async checkpoints, restart/replay, straggler monitor).
It runs on one device: reduced configs end-to-end on a CPU (see
examples/train_lm.py), full configs on one accelerator.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --steps 100 --batch 8 --seq 128

A previously verified offload plan (committed by an ``OffloadSession``,
e.g. the ``repro.offload.zoo`` sweep) can be bound at startup with
--plan-dir/--plan-key — the step is then traced under that block->target
pattern with zero search or re-measurement.  With ``--plan-dir`` alone the
stored ``zoo:<arch>:train`` plan (when present) binds automatically;
``--plan-search`` searches and commits a missing plan first (using
``--executor`` to parallelise the measurement), and ``--meter`` reports the
run's power telemetry with measured/estimated provenance.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import SyntheticLMData
from repro.checkpoint.manager import CheckpointManager
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import TrainHyper, make_train_step
from repro.models import lm
from repro.optim.adamw import AdamW
from repro.runtime.fault import FaultTolerantLoop
from repro.runtime.monitor import StepMonitor


@dataclasses.dataclass
class TrainState:
    params: object
    opt_state: object


def build(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        cfg = dataclasses.replace(
            cfg, n_layers=args.layers,
            block_pattern=None if cfg.block_pattern is None
            else cfg.pattern()[: args.layers],
        )
    data = SyntheticLMData(
        vocab_size=cfg.vocab_size,
        seq_len=args.seq,
        global_batch=args.batch,
        seed=args.seed,
    )
    opt = AdamW(moment_dtype=cfg.opt_dtype)
    hyper = TrainHyper(
        base_lr=args.lr, warmup_steps=min(50, args.steps // 10 + 1),
        total_steps=args.steps, microbatch=args.microbatch,
    )
    step_fn = jax.jit(make_train_step(cfg, opt, hyper), donate_argnums=(0, 1))
    params = lm.init_params(cfg, seed=args.seed)
    opt_state = opt.init(params)
    return cfg, data, step_fn, params, opt_state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--plan-dir", default=None,
                    help="PlanStore directory with verified offload plans")
    ap.add_argument("--plan-key", default=None,
                    help="plan to load and bind at startup (zero search); "
                         "defaults to the stored zoo:<arch>:train plan "
                         "when present")
    ap.add_argument("--plan-search", action="store_true",
                    help="search+commit a missing zoo:<arch>:train plan "
                         "before binding (verification-environment step)")
    ap.add_argument("--plan-targets", default="ref,xla",
                    help="targets --plan-search searches over "
                         "(add 'pallas' on TPU hosts)")
    ap.add_argument("--executor", default="serial",
                    help="measurement executor for --plan-search: serial | "
                         "device-parallel | batched")
    ap.add_argument("--meter", default="none",
                    help="power telemetry for the run (and --plan-search): "
                         "none | auto | time | nvml | rapl | psutil")
    args = ap.parse_args()
    enable_compile_cache()

    from repro.metering import meter_window, resolve_meter

    if args.plan_dir and not args.plan_key:
        from repro.offload.zoo import launch_plan_keys

        args.plan_key = launch_plan_keys(
            args.plan_dir,
            args.arch,
            ("train",),
            search=args.plan_search,
            targets=tuple(args.plan_targets.split(",")),
            executor=args.executor,
            meter=args.meter,
        )["train"]
        if args.plan_key is None:
            # dir-without-key is a legitimate "bind defaults when present"
            # configuration now; don't let attach print noise about it
            args.plan_dir = None
    meter = resolve_meter(args.meter)

    cfg, data, step_fn, params, opt_state = build(args)
    print(f"arch={cfg.name} params={lm.pm.count_params(lm.build_metas(cfg))/1e6:.1f}M")

    monitor = StepMonitor()
    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    state = {"params": params, "opt": opt_state}
    last_metrics = {}

    def one_step(state, batch, step):
        nonlocal last_metrics
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt_state, metrics = step_fn(state["params"], state["opt"], b)
        last_metrics = jax.device_get(metrics)
        if step % args.log_every == 0:
            print(
                f"step {step:5d} loss {float(last_metrics['loss']):.4f} "
                f"({monitor.median_step()*1e3:.0f} ms/step)",
                flush=True,
            )
        return {"params": params, "opt": opt_state}

    loop = FaultTolerantLoop(
        step_fn=one_step,
        batch_fn=data.batch_at,
        ckpt=ckpt,
        ckpt_every=args.ckpt_every,
        monitor=monitor,
    )
    from repro.offload import OffloadSession

    t0 = time.time()
    with OffloadSession.attach(args.plan_dir, args.plan_key):
        with meter_window(meter) as tele:
            result = loop.run(state, args.steps)
    dt = time.time() - t0
    tokens = args.steps * args.batch * args.seq
    print(
        f"done: {result.completed_steps} steps, {result.restarts} restarts, "
        f"final loss {float(last_metrics.get('loss', np.nan)):.4f}, "
        f"{tokens/dt:.0f} tok/s"
    )
    if meter is not None:
        print(f"power: train loop {tele.summary()}")


if __name__ == "__main__":
    main()
