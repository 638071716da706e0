"""Unified decoder LM covering all 10 assigned architectures.

A model is a *block pattern* — one char per layer:
    'a'  attention + (MoE if configured, else SwiGLU MLP)
    'd'  attention + dense MLP (the leading dense layers of an MoE stack)
    'm'  Mamba-2 SSD block
    's'  shared-parameter attention+MLP block (Zamba2) — one param set,
         applied at every 's' site (each site keeps its own KV cache)

Consecutive identical chars form a *group*; each group's parameters are
stacked with a leading layer axis and executed with ``lax.scan`` so compile
time and HLO size are O(#groups), not O(#layers).  Shared blocks are applied
point-wise between groups with the single shared param set.

Modes: train (loss), prefill (build cache + logits), decode (one token
against the cache).  Caches/states are stacked per group, mirroring the
param stacking.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

import repro.kernels  # noqa: F401  (registers function blocks)

# Remat policy for the per-layer checkpoint: "none" = recompute everything
# (the paper-faithful baseline), "save_moe" = keep each MoE block's output
# (a small (B,S,D) bf16 per layer) so the backward never re-runs the expert
# forward — each re-run costs a full FSDP gather of the expert weights, the
# dominant collective for 100B+ MoE models (a §Perf knob).
REMAT_POLICY = "none"
from repro.configs.base import ArchConfig
from repro.models import params as pm
from repro.models.attention import (
    attention_forward,
    attn_metas,
    cache_metas,
    cache_metas_paged,
)
from repro.models.layers import (
    cross_entropy,
    embed_lookup,
    embed_metas,
    lm_logits,
    mlp_forward,
    mlp_metas,
    rmsnorm,
)
from repro.models.moe import moe_forward, moe_metas
from repro.models.params import ParamMeta
from repro.models.ssm import ssm_forward, ssm_metas, ssm_state_metas
from repro.sharding.utils import constrain


def _no_load() -> jax.Array:
    """The MoE load of a layer without routed experts."""
    return jnp.zeros((2,), jnp.int32)


# -- pattern grouping -----------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Group:
    index: int
    kind: str  # 'a' | 'd' | 'm' | 's'
    count: int

    @property
    def key(self) -> str:
        return f"g{self.index}_{self.kind}"


def groups_of(cfg: ArchConfig) -> list[Group]:
    pat = cfg.pattern()
    out: list[Group] = []
    i = 0
    gi = 0
    while i < len(pat):
        j = i
        while j < len(pat) and pat[j] == pat[i]:
            j += 1
        out.append(Group(gi, pat[i], j - i))
        gi += 1
        i = j
    return out


# -- parameter metas --------------------------------------------------------------


def _stack(metas: Any, n: int) -> Any:
    return pm.tree_map_metas(
        lambda m: ParamMeta(
            (n,) + m.shape, ("layers",) + m.axes, m.dtype, m.init, m.scale
        ),
        metas,
    )


def _block_metas(cfg: ArchConfig, kind: str) -> dict:
    d = cfg.d_model
    dt = cfg.param_dtype
    if kind == "m":
        return {
            "ln": ParamMeta((d,), (None,), dt, init="ones"),
            "mixer": ssm_metas(cfg),
        }
    metas = {
        "ln1": ParamMeta((d,), (None,), dt, init="ones"),
        "attn": attn_metas(cfg),
        "ln2": ParamMeta((d,), (None,), dt, init="ones"),
    }
    if kind == "a" and cfg.moe is not None:
        metas["moe"] = moe_metas(cfg)
    else:
        metas["mlp"] = mlp_metas(d, cfg.d_ff, dt)
    return metas


def build_metas(cfg: ArchConfig) -> dict:
    metas: dict = {"embed": embed_metas(cfg)}
    blocks: dict = {}
    has_shared = False
    for g in groups_of(cfg):
        if g.kind == "s":
            has_shared = True
            continue
        blocks[g.key] = _stack(_block_metas(cfg, g.kind), g.count)
    if has_shared:
        metas["shared_block"] = _block_metas(cfg, "s")
    metas["blocks"] = blocks
    metas["final_norm"] = ParamMeta(
        (cfg.d_model,), (None,), cfg.param_dtype, init="ones"
    )
    return metas


def cache_metas_tree(
    cfg: ArchConfig,
    batch: int,
    max_len: int,
    *,
    page_size: int | None = None,
    n_pages: int | None = None,
) -> dict:
    """Cache layout: contiguous (default) or block-paged.

    Contiguous: every attention group leaf reserves ``batch x max_len``
    rows.  Paged (``page_size`` + ``n_pages`` given): attention leaves
    become a shared pool of ``n_pages`` fixed-size pages (+ one null page
    at index ``n_pages``), addressed through the ``(batch, max_pages)``
    page table the serving engine passes alongside the cache; SSM state
    leaves stay per-slot (a recurrent state has no sequence axis to page).
    """
    paged = page_size is not None
    if paged and n_pages is None:
        raise ValueError("paged cache needs both page_size and n_pages")
    caches: dict = {}
    for g in groups_of(cfg):
        if g.kind == "m":
            caches[g.key] = _stack(ssm_state_metas(cfg, batch), g.count)
        elif paged:
            caches[g.key] = _stack(
                cache_metas_paged(cfg, n_pages + 1, page_size), g.count
            )
        else:
            caches[g.key] = _stack(cache_metas(cfg, batch, max_len), g.count)
    # per-slot lengths: continuous-batching serving staggers requests
    # across batch rows, so each row carries its own write position
    caches["index"] = ParamMeta((batch,), ("act_batch",), "int32", init="zeros")
    return caches


def init_params(cfg: ArchConfig, seed: int = 0) -> Any:
    return pm.init_params(build_metas(cfg), seed)


def init_cache(
    cfg: ArchConfig,
    batch: int,
    max_len: int,
    *,
    page_size: int | None = None,
    n_pages: int | None = None,
) -> Any:
    return pm.init_params(
        cache_metas_tree(
            cfg, batch, max_len, page_size=page_size, n_pages=n_pages
        ),
        0,
    )


# -- block application -------------------------------------------------------------


def _apply_attn_block(
    lp: dict, x: jax.Array, cfg: ArchConfig, positions, cache, index, mode,
    kind: str, pages=None, layer=None,
):
    cd = jnp.dtype(cfg.compute_dtype)
    # Sequence-parallel <-> tensor-parallel transitions are made explicit
    # and pinned to the bf16 side of the norm: the all-gather to full
    # sequence happens on the bf16 post-norm activation (not the f32 norm
    # internals XLA would otherwise hoist it above), and mixer/FFN outputs
    # are constrained straight back to sequence shards so GSPMD emits
    # reduce-scatters instead of all-reduce + re-slice.
    # barrier after the bf16 cast: the SP->TP all-gather must happen on
    # the bf16 post-norm tensor, not be hoisted above the cast into the
    # norm's f32 internals (which doubles transition bytes)
    h_in = jax.lax.optimization_barrier(
        rmsnorm(lp["ln1"], x, cfg.norm_eps).astype(cd)
    )
    attn_out, new_cache = attention_forward(
        lp["attn"], h_in, cfg, positions, cache, index, mode, pages, layer
    )
    x = x + attn_out.astype(x.dtype)
    ff_in = jax.lax.optimization_barrier(
        rmsnorm(lp["ln2"], x, cfg.norm_eps).astype(cd)
    )
    if kind == "a" and cfg.moe is not None:
        ff, aux, load = moe_forward(lp["moe"], ff_in, cfg, cd, mode)
    else:
        with jax.named_scope("mlp"):
            ff = mlp_forward(lp["mlp"], ff_in, cd)
        aux, load = jnp.asarray(0.0, jnp.float32), _no_load()
    x = x + ff.astype(x.dtype)
    x = constrain(x, "act_batch", "act_seq", None)
    return x, aux, load, new_cache


def _apply_mamba_block(lp, x, cfg, cache, mode):
    if mode == "extend":
        raise ValueError(
            "chunked prefill (extend mode) is unsupported for SSM blocks: "
            "resuming the scan needs the conv window stitched across chunk "
            "boundaries"
        )
    cd = jnp.dtype(cfg.compute_dtype)
    h_in = jax.lax.optimization_barrier(
        rmsnorm(lp["ln"], x, cfg.norm_eps).astype(cd)
    )
    out, new_state = ssm_forward(lp["mixer"], h_in, cfg, cache, mode)
    x = x + out.astype(x.dtype)
    x = constrain(x, "act_batch", "act_seq", None)
    return x, jnp.asarray(0.0, jnp.float32), _no_load(), new_state


def _apply_group(
    gparams, g: Group, x, cfg, positions, gcache, index, mode, shared_params,
    pages=None,
):
    """Scan a homogeneous group of layers; returns (x, aux_sum, load_sum,
    new_gcache).

    A paged attention group's cache (``pages`` given) rides in the scan's
    carry, not its ``xs``/``ys``: layer ``i`` writes its new token rows at
    ``(i, page, row)`` of the stacked pools and reads ``pool[i, pages]``
    inside its page walk, so no layer's pool is sliced out and no stacked
    pool is re-emitted — with the cache donated, the pools are updated in
    place.  Other caches are scanned per layer."""
    use_cache = gcache is not None
    shared = g.kind == "s"
    carried = use_cache and pages is not None and g.kind != "m"

    def apply_one(x, aux, load, lp, lcache, li=None):
        p = shared_params if shared else lp
        if g.kind == "m":
            x, a, ld, nc = _apply_mamba_block(p, x, cfg, lcache, mode)
        else:
            x, a, ld, nc = _apply_attn_block(
                p, x, cfg, positions, lcache, index, mode, g.kind, pages, li
            )
        return x, aux + a, load + ld, nc

    def layer(carry, xs):
        x, aux, load, pool = carry
        # barrier: prevents XLA from hoisting dtype converts of the stacked
        # layer-input residuals out of the scan (an f32 copy of every
        # saved carry doubles remat memory otherwise)
        x = jax.lax.optimization_barrier(x)
        li = None
        if carried:
            lp, li = (None, xs) if shared else xs
            lcache = pool
        elif shared:
            lp, lcache = None, xs
        elif use_cache:
            lp, lcache = xs
        else:
            lp, lcache = xs, None
        x, aux, load, nc = apply_one(x, aux, load, lp, lcache, li)
        if carried:
            return (x, aux, load, nc), None
        return (x, aux, load, None), nc

    body = layer
    if cfg.remat == "full" and mode == "train":
        policy = None
        if REMAT_POLICY == "save_moe" and cfg.moe is not None:
            policy = jax.checkpoint_policies.save_only_these_names("moe_out")
        body = jax.checkpoint(layer, prevent_cse=False, policy=policy)

    zero = jnp.asarray(0.0, jnp.float32)
    if shared and not use_cache:
        # cache-less shared blocks: unrolled application (count is small
        # and there are no per-site parameters to stack)
        aux_t, load_t = zero, _no_load()
        for _ in range(g.count):
            x, aux_t, load_t, _ = apply_one(x, aux_t, load_t, None, None)
        return x, aux_t, load_t, None

    if carried:
        layers = jnp.arange(g.count, dtype=jnp.int32)
        xs = layers if shared else (gparams, layers)
        (x, aux, load, new_cache), _ = jax.lax.scan(
            body, (x, zero, _no_load(), gcache), xs
        )
        return x, aux, load, new_cache
    if shared:
        xs = gcache  # scan each site's cache under the shared params
    elif use_cache:
        xs = (gparams, gcache)
    else:
        xs = gparams
    (x, aux, load, _), new_cache = jax.lax.scan(
        body, (x, zero, _no_load(), None), xs
    )
    return x, aux, load, (new_cache if use_cache else None)


# -- forward / loss / serve ---------------------------------------------------------


def _input_embeds(params, batch, cfg: ArchConfig):
    cd = jnp.dtype(cfg.compute_dtype)
    if "embeds" in batch:
        return batch["embeds"].astype(cd)
    return embed_lookup(params["embed"], batch["tokens"], cd)


def backbone(
    params: Any,
    batch: dict,
    cfg: ArchConfig,
    mode: str = "train",
    cache: Any = None,
):
    """All blocks, no head.  Returns (hidden (B,S,D), aux_loss, new_cache,
    moe_load): ``moe_load`` (2,) int32 sums, over the MoE layers, the
    serving dispatch's (token, held expert) pairs and each layer's busiest
    held expert's pairs (zeros without MoE layers or in training)."""
    x = _input_embeds(params, batch, cfg)
    b, s = x.shape[0], x.shape[1]
    x = constrain(x, "act_batch", "act_seq", None)

    pages = None
    if mode in ("decode", "extend"):
        index = cache["index"]
        if index.ndim != 1:
            raise ValueError(
                "cache['index'] must be per-slot (B,) write positions; the "
                "scalar-index broadcast fallback was removed — rebuild the "
                "cache with init_cache()"
            )
        index = index.astype(jnp.int32)
        pages = cache.get("pages")  # (B, max_pages) page table, paged only
        positions = index[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    else:
        index = None
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, :], (b, s)
        )

    aux_total = jnp.asarray(0.0, jnp.float32)
    load_total = _no_load()
    new_cache: dict = {} if cache is not None else None
    shared = params.get("shared_block")
    for g in groups_of(cfg):
        gparams = None if g.kind == "s" else params["blocks"][g.key]
        gcache = cache[g.key] if cache is not None else None
        x, aux, load, nc = _apply_group(
            gparams, g, x, cfg, positions, gcache, index, mode, shared, pages
        )
        aux_total = aux_total + aux
        load_total = load_total + load
        if cache is not None:
            new_cache[g.key] = nc
    return x, aux_total, new_cache, load_total


@jax.named_scope("head")
def head(params: Any, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return lm_logits(params["embed"], x, cfg, jnp.dtype(cfg.compute_dtype))


def forward(
    params: Any,
    batch: dict,
    cfg: ArchConfig,
    mode: str = "train",
    cache: Any = None,
):
    """Returns (logits, aux_loss, new_cache)."""
    return _forward(params, batch, cfg, mode, cache)[:3]


def _forward(params, batch, cfg, mode, cache):
    """``forward`` and the MoE load (see ``backbone``)."""
    x, aux_total, new_cache, load = backbone(params, batch, cfg, mode, cache)
    s = x.shape[1]
    logits = head(params, x, cfg)
    if cache is not None:
        if mode in ("decode", "extend"):
            new_cache["index"] = cache["index"] + s
        else:  # prefill: every row's cache now holds s tokens
            new_cache["index"] = jnp.full(
                (batch["tokens" if "tokens" in batch else "embeds"].shape[0],),
                s, jnp.int32,
            )
    return logits, aux_total, new_cache, load


def loss_fn(params: Any, batch: dict, cfg: ArchConfig):
    x, aux, _, _ = backbone(params, batch, cfg, mode="train")

    def head_loss(p, xx, labels):
        logits = head(p, xx, cfg)
        return cross_entropy(logits, labels)

    # remat the head: the (B,S,V) logits/softmax residuals are the largest
    # single activations in the step; recomputing one matmul in the backward
    # is far cheaper than holding them
    if cfg.remat == "full":
        head_loss = jax.checkpoint(head_loss)
    ce = head_loss(params, x, batch["labels"])
    coef = cfg.moe.aux_loss_coef if cfg.moe else 0.0
    total = ce + coef * aux
    return total, {"loss": total, "ce": ce, "aux": aux}


def prefill(params: Any, batch: dict, cfg: ArchConfig, cache: Any):
    logits, _, new_cache = forward(params, batch, cfg, mode="prefill", cache=cache)
    return logits, new_cache


def decode_step(params: Any, tokens: jax.Array, cfg: ArchConfig, cache: Any):
    """tokens (B, 1) -> (logits (B,1,V), new_cache, moe_load).
    cache["index"] (B,) is each row's write position for this token — rows
    may sit at different positions (continuous batching).  ``moe_load`` is
    ``backbone``'s (zeros without MoE layers)."""
    logits, _, new_cache, load = _forward(
        params, {"tokens": tokens}, cfg, "decode", cache
    )
    return logits, new_cache, load
