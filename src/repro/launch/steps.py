"""Step builders shared by the trainer, the server and the dry-run.

Each builder returns a pure function suitable for jax.jit with explicit
in/out shardings; abstract-value builders produce the matching
ShapeDtypeStruct trees (``input_specs``) so the dry-run lowers the exact
production program with zero allocation.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import lm
from repro.models import params as pm
from repro.optim.adamw import AdamW, OptState
from repro.optim.schedule import warmup_cosine


@dataclasses.dataclass(frozen=True)
class TrainHyper:
    base_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    microbatch: int | None = None  # grad-accumulation chunks of the batch


# -- abstract inputs (the dry-run contract) -------------------------------------


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    tok = lambda bb, ss: jax.ShapeDtypeStruct((bb, ss), jnp.int32)
    if shape.kind == "train":
        if cfg.frontend == "patch_embed":
            return {
                "embeds": jax.ShapeDtypeStruct(
                    (b, s, cfg.d_model), jnp.dtype(cfg.compute_dtype)
                ),
                "labels": tok(b, s),
            }
        return {"tokens": tok(b, s), "labels": tok(b, s)}
    if shape.kind == "prefill":
        if cfg.frontend == "patch_embed":
            return {
                "embeds": jax.ShapeDtypeStruct(
                    (b, s, cfg.d_model), jnp.dtype(cfg.compute_dtype)
                )
            }
        return {"tokens": tok(b, s)}
    # decode: one new token; the seq_len lives in the cache
    return {"tokens": tok(b, 1)}


def abstract_state(cfg: ArchConfig, opt: AdamW | None = None):
    """(params, opt_state) as ShapeDtypeStructs."""
    metas = lm.build_metas(cfg)
    params = pm.abstract_params(metas)
    if opt is None:
        return params, None
    mdt = jnp.dtype(opt.moment_dtype)
    mom = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, mdt), params)
    opt_state = OptState(
        mu=mom,
        nu=jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, mdt), params),
        step=jax.ShapeDtypeStruct((), jnp.int32),
    )
    return params, opt_state


def abstract_cache(
    cfg: ArchConfig,
    shape: ShapeConfig,
    page_size: int | None = None,
    n_pages: int | None = None,
):
    """Abstract KV cache for this cell — contiguous, or block-paged when
    ``page_size``/``n_pages`` are given.  The paged tree includes the
    ``pages`` page-table operand (``(B, max_pages)`` int32) the decode
    program gathers through, so the dry-run lowers the exact paged
    serving program with zero allocation."""
    metas = lm.cache_metas_tree(
        cfg, shape.global_batch, shape.seq_len,
        page_size=page_size, n_pages=n_pages,
    )
    tree = pm.abstract_params(metas)
    if page_size is not None:
        max_pages = -(-shape.seq_len // page_size)
        tree["pages"] = jax.ShapeDtypeStruct(
            (shape.global_batch, max_pages), jnp.int32
        )
    return tree


# -- steps ------------------------------------------------------------------------


def make_train_step(
    cfg: ArchConfig,
    opt: AdamW,
    hyper: TrainHyper = TrainHyper(),
    grad_shardings: Any = None,
):
    """``grad_shardings``: optional pytree of NamedSharding matching params.
    Constraining gradients to the parameter sharding makes GSPMD emit
    reduce-scatters into the ZeRO shards instead of all-reducing the full
    replicated gradient tree (at 35 GB+ of f32 grads the difference is the
    entire collective budget of the step)."""

    def loss_of(params, batch):
        return lm.loss_fn(params, batch, cfg)

    def _constrain_grads(grads):
        if grad_shardings is None:
            return grads
        return jax.tree.map(
            jax.lax.with_sharding_constraint, grads, grad_shardings
        )

    def train_step(params, opt_state, batch):
        if hyper.microbatch and hyper.microbatch > 1:
            n = hyper.microbatch

            def micro(carry, mb):
                acc, metr_acc = carry
                (_, metrics), grads = jax.value_and_grad(
                    loss_of, has_aux=True
                )(params, mb)
                grads = _constrain_grads(grads)
                acc = jax.tree.map(jnp.add, acc, grads)
                metr_acc = jax.tree.map(jnp.add, metr_acc, metrics)
                return (acc, metr_acc), None

            mbs = jax.tree.map(
                lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch
            )
            # accumulate grads in the moment dtype: a full f32 grad tree is
            # 4 bytes/param resident for the whole step — at 480B params
            # ZeRO-sharded over 256 chips that alone is 7.5 GB/chip
            acc_dt = jnp.dtype(opt.moment_dtype)
            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dt), params
            )
            zero_m = {"loss": 0.0, "ce": 0.0, "aux": 0.0}
            zero_m = jax.tree.map(jnp.float32, zero_m)
            (grads, metrics), _ = jax.lax.scan(micro, (zero_g, zero_m), mbs)
            grads = jax.tree.map(lambda g: g / n, grads)
            metrics = jax.tree.map(lambda m: m / n, metrics)
        else:
            (_, metrics), grads = jax.value_and_grad(loss_of, has_aux=True)(
                params, batch
            )
            grads = _constrain_grads(grads)
        lr = warmup_cosine(
            opt_state.step, hyper.base_lr, hyper.warmup_steps, hyper.total_steps
        )
        params, opt_state = opt.update(grads, opt_state, params, lr)
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ArchConfig, shape: ShapeConfig):
    cache_metas = lm.cache_metas_tree(cfg, shape.global_batch, shape.seq_len)

    def prefill_step(params, batch):
        cache = pm.init_params(cache_metas, 0)  # zeros (+ index 0)
        # serving samples from the LAST position only: run the backbone over
        # the full prompt but project just the final hidden state — the full
        # (B, S, V) logits tensor (tens of GB at 32k x 128k-vocab) is never
        # materialised.
        x, _, new_cache, _ = lm.backbone(params, batch, cfg, "prefill", cache)
        logits_last = lm.head(params, x[:, -1:, :], cfg)
        new_cache["index"] = jnp.full(
            (shape.global_batch,), shape.seq_len, jnp.int32
        )
        return logits_last[:, 0, :], new_cache

    return prefill_step


def make_decode_step(cfg: ArchConfig):
    def decode_step(params, cache, batch):
        logits, new_cache, _ = lm.decode_step(params, batch["tokens"], cfg, cache)
        return logits[:, 0, :], new_cache

    return decode_step
