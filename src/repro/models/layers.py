"""Shared layer primitives: norms, RoPE, SwiGLU MLP, embeddings.

Compute flows through the FunctionBlock registry (``blocks.call``) wherever a
shelf kernel exists, so the offload engine can re-bind implementations.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, YarnScaling
from repro.core import blocks
from repro.models.params import ParamMeta
from repro.sharding.utils import constrain

# Tensor-parallel output projections (attention wo, MLP down, SSM out):
# False = leave the contraction to GSPMD, which all-reduces the f32 partial
# sums (structural: the partitioner places the reduction before the bf16
# rounding and no jaxpr-level cast changes that).  True = take manual
# control via shard_map: per-shard matmul with f32 MXU accumulation, round
# the partial to bf16, then psum_scatter it in bf16 directly into the
# sequence-parallel shards — one RS of bf16 instead of one AR of f32, an
# ~8x cut of the dominant TP collective (a §Perf knob).
BF16_TP_REDUCE = False


def tp_out_einsum(spec: str, a: jax.Array, b: jax.Array, cd) -> jax.Array:
    """Einsum 'bsq,qd->bsd'-shaped, contraction crossing the TP shards."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.utils import current_mesh, current_rules, resolve_spec

    mesh = current_mesh()
    if (
        not BF16_TP_REDUCE
        or mesh is None
        or "model" not in mesh.axis_names
        or a.ndim != 3
    ):
        return jnp.einsum(spec, a, b)
    rules = current_rules()
    batch_spec = resolve_spec(("act_batch",), rules)[0]
    seq_rule = rules.get("act_seq")
    scatter_seq = seq_rule == "model" and a.shape[1] % mesh.shape["model"] == 0

    in_a = P(batch_spec, None, "model")
    in_b = P("model", None)
    out = P(batch_spec, "model" if scatter_seq else None, None)

    def local(a_l, b_l):
        part = jnp.einsum(
            spec, a_l, b_l, preferred_element_type=jnp.float32
        ).astype(cd)
        if scatter_seq:
            return jax.lax.psum_scatter(
                part, "model", scatter_dimension=1, tiled=True
            )
        return jax.lax.psum(part, "model")

    return jax.shard_map(
        local, mesh=mesh, in_specs=(in_a, in_b), out_specs=out,
        check_vma=False,
    )(a, b)


def rmsnorm(w: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return blocks.call("rmsnorm", x, w, eps=eps)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-magnitude factor ``0.1 * mscale * ln(factor) + 1``
    (1 at ``factor`` <= 1)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_range(d: int, theta: float, scaling: YarnScaling) -> tuple[int, int]:
    """(low, high) rope channel pairs of YaRN's ramp, clamped to
    ``[0, d - 1]``: pair ``i`` turns ``r`` times over the original context
    at ``i = d * ln(L / (2 pi r)) / (2 ln theta)``; ``low`` is where it
    turns ``beta_fast`` times (rounded down), ``high`` ``beta_slow``
    times (rounded up)."""
    length = scaling.original_max_position_embeddings

    def at(turns):
        return d * math.log(length / (turns * 2 * math.pi)) / (2 * math.log(theta))

    return (max(math.floor(at(scaling.beta_fast)), 0),
            min(math.ceil(at(scaling.beta_slow)), d - 1))


def rope_inv_freq(d: int, theta: float,
                  scaling: YarnScaling | None = None) -> jax.Array:
    """(d/2,) float32 inverse frequencies ``theta^(-2i/d)``; under YaRN,
    ``f_inter (1 - m) + f_extra m`` with ``f_extra = theta^(-2i/d)``,
    ``f_inter = f_extra / factor`` and ``m = 1 - clip((i - low) / (high -
    low), 0, 1)``."""
    half = d // 2
    extra = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if scaling is None:
        return extra
    low, high = yarn_range(d, theta, scaling)
    ramp = (jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 1e-3)
    m = 1.0 - jnp.clip(ramp, 0.0, 1.0)
    return extra / scaling.factor * (1.0 - m) + extra * m


def rope(x: jax.Array, positions: jax.Array, theta: float,
         scaling: YarnScaling | None = None) -> jax.Array:
    """Rotary embedding, llama-style rotate-half; YaRN frequencies and
    cos/sin magnitude under ``scaling``.

    x: (B, S, H, d); positions: (B, S) int32.
    """
    d = x.shape[-1]
    half = d // 2
    freqs = rope_inv_freq(d, theta, scaling)  # (half,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :]  # (B,S,1,half)
    sin = jnp.sin(ang)[:, :, None, :]
    if scaling is not None:
        mag = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
            scaling.factor, scaling.mscale_all_dim
        )
        if mag != 1.0:
            cos, sin = cos * mag, sin * mag
    x1, x2 = x[..., :half], x[..., half:]
    xf1 = x1.astype(jnp.float32)
    xf2 = x2.astype(jnp.float32)
    out = jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# -- SwiGLU MLP ----------------------------------------------------------------


def mlp_metas(d_model: int, d_ff: int, dtype: str) -> dict:
    return {
        "gate": ParamMeta((d_model, d_ff), ("embed", "ffn"), dtype),
        "up": ParamMeta((d_model, d_ff), ("embed", "ffn"), dtype),
        "down": ParamMeta((d_ff, d_model), ("ffn", "embed"), dtype),
    }


# True = the whole SwiGLU MLP runs as one shard_map: all-gather the bf16
# sequence shards once, compute gate/up/silu/down on the local FFN shard,
# psum_scatter the bf16 output back to sequence shards.  Exactly Megatron
# TP+SP: 1 AG(bf16) + 1 RS(bf16) per MLP, and the FSDP weight gathers at
# the shard_map boundary move bf16 — versus GSPMD's 2 AG(f32) + AR(f32).
MEGATRON_MLP = False


def _megatron_mlp(p: dict, x: jax.Array, cd) -> jax.Array:
    from jax.sharding import PartitionSpec as P

    from repro.sharding.utils import current_mesh, current_rules, resolve_spec

    mesh = current_mesh()
    rules = current_rules()
    batch_spec = resolve_spec(("act_batch",), rules)[0]
    tp = mesh.shape["model"]
    seq_sharded = rules.get("act_seq") == "model" and x.shape[1] % tp == 0

    xs = P(batch_spec, "model" if seq_sharded else None, None)

    def local(x_l, g_l, u_l, d_l):
        if seq_sharded:
            x_full = jax.lax.all_gather(x_l, "model", axis=1, tiled=True)
        else:
            x_full = x_l
        g = jnp.einsum("bsd,df->bsf", x_full, g_l)
        u = jnp.einsum("bsd,df->bsf", x_full, u_l)
        h = jax.nn.silu(g) * u
        part = jnp.einsum(
            "bsf,fd->bsd", h, d_l, preferred_element_type=jnp.float32
        ).astype(cd)
        if seq_sharded:
            return jax.lax.psum_scatter(
                part, "model", scatter_dimension=1, tiled=True
            )
        return jax.lax.psum(part, "model")

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(xs, P(None, "model"), P(None, "model"), P("model", None)),
        out_specs=xs,
        check_vma=False,
    )(
        x.astype(cd),
        p["gate"].astype(cd),
        p["up"].astype(cd),
        p["down"].astype(cd),
    )


def mlp_forward(p: dict, x: jax.Array, compute_dtype) -> jax.Array:
    from repro.sharding.utils import current_mesh

    if MEGATRON_MLP and current_mesh() is not None and (
        "model" in current_mesh().axis_names
    ):
        return _megatron_mlp(p, x, compute_dtype)
    xc = x.astype(compute_dtype)
    g = jnp.einsum("bsd,df->bsf", xc, p["gate"].astype(compute_dtype))
    u = jnp.einsum("bsd,df->bsf", xc, p["up"].astype(compute_dtype))
    h = jax.nn.silu(g) * u
    h = constrain(h, "act_batch", None, "ffn_act")
    return tp_out_einsum("bsf,fd->bsd", h, p["down"].astype(compute_dtype),
                         compute_dtype)


# -- embeddings -----------------------------------------------------------------


def embed_metas(cfg: ArchConfig) -> dict:
    d = {
        "embedding": ParamMeta(
            (cfg.padded_vocab, cfg.d_model), ("vocab", "embed"), cfg.param_dtype,
            scale=0.02,
        )
    }
    if not cfg.tie_embeddings:
        d["lm_head"] = ParamMeta(
            (cfg.d_model, cfg.padded_vocab), ("embed", "vocab"), cfg.param_dtype,
            scale=0.02,
        )
    return d


def embed_lookup(p: dict, tokens: jax.Array, compute_dtype) -> jax.Array:
    emb = p["embedding"].astype(compute_dtype)
    return emb[tokens]


def lm_logits(p: dict, x: jax.Array, cfg: ArchConfig, compute_dtype) -> jax.Array:
    if cfg.tie_embeddings:
        w = p["embedding"].astype(compute_dtype).T
    else:
        w = p["lm_head"].astype(compute_dtype)
    logits = jnp.einsum("bsd,dv->bsv", x.astype(compute_dtype), w)
    return constrain(logits, "act_batch", None, "vocab")


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean next-token cross entropy; logits (B,S,V), labels (B,S).

    Formulated with a one-hot contraction (not take_along_axis): a gather
    over a vocab-sharded logits tensor makes GSPMD replicate the full vocab
    dimension per device (tens of GB at 128k vocab); the one-hot form fuses
    into a sharded partial reduction instead.
    """
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    logz = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    gold = jnp.sum(shifted * onehot, axis=-1)
    return jnp.mean(logz - gold)
