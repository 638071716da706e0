"""Mixture-of-Experts FFN: one router, two dispatches.

The router scores every expert of the layer (softmax in float32), keeps
each token's top-k greedily and, unless ``norm_topk_prob`` is false,
renormalises their weights to sum to 1.  A device may hold only a share
of the experts, ``[experts_first, experts_first + held)`` (expert
parallelism's share of one device): the router still scores all of them,
and a token's pairs with experts held elsewhere add nothing here.

* Serving (``prefill``, ``extend``, ``decode``) is dropless: every
  (token, held expert) pair is computed, whatever else shares the batch,
  so a served token's output does not depend on its neighbours.  The
  pairs are sorted by expert and each expert weight runs as one grouped
  product over them (``jax.lax.ragged_dot``); a token's output is the
  weighted sum of its pairs.  No (tokens, experts, capacity) table is
  built, and a decode step computes about ``B * top_k * held / E`` pairs.
* Training routes per batch row (the GShard "group") with a capacity:
  every row routes its S tokens into an (E, C) index table
  (C = S*top_k/E*capacity_factor), and tokens beyond capacity are dropped
  (their index points at the out-of-range sentinel and the gather/scatter
  drop it).  Dispatch is a *gather* and combine is a *scatter-add* — no
  dense (tokens, E, C) one-hot tensor is ever materialised, which is what
  lets arctic-480b's 128-expert layers run at 1M tokens/step.  Training
  keeps this table because its experts are sharded over "model" by GSPMD:
  rows over "data", experts over "model", the dispatch gather row-local,
  and the expert einsum lowers to the expected all-to-alls, which a
  ragged grouped product over a sharded expert axis does not.

Supports shared experts (DeepSeek: always-on) and a dense residual FFN in
parallel (Arctic).  Aux loss is the Switch load-balancing loss (training).
The layer runs under the ``moe`` scope, its router under ``router`` and
its expert products (shared experts included) under ``experts``.
"""

from __future__ import annotations

import math

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.models.layers import mlp_forward, mlp_metas, tp_out_einsum
from repro.models.params import ParamMeta
from repro.sharding.utils import constrain


def moe_metas(cfg: ArchConfig) -> dict:
    m = cfg.moe
    d = cfg.d_model
    dt = cfg.param_dtype
    metas = {
        "router": ParamMeta((d, m.n_experts), ("embed", None), dt, scale=0.02),
        "w_gate": ParamMeta(
            (m.held, d, m.d_expert), ("experts", "expert_in", "expert_ffn"), dt
        ),
        "w_up": ParamMeta(
            (m.held, d, m.d_expert), ("experts", "expert_in", "expert_ffn"), dt
        ),
        "w_down": ParamMeta(
            (m.held, m.d_expert, d), ("experts", "expert_ffn", "expert_in"), dt
        ),
    }
    if m.n_shared:
        metas["shared"] = mlp_metas(d, m.d_expert * m.n_shared, dt)
    if m.dense_residual:
        metas["dense"] = mlp_metas(d, cfg.d_ff, dt)
    return metas


def capacity_of(seq: int, m: MoEConfig) -> int:
    c = int(math.ceil(seq * m.top_k / m.n_experts * m.capacity_factor))
    return max(4, ((c + 3) // 4) * 4)


def route(p: dict, x: jax.Array, m: MoEConfig):
    """The router over every expert of the layer, for tokens x (..., D).

    Returns (gates (..., E) f32 softmax, topv (..., k) f32 combine
    weights, topi (..., k) int32 global expert ids), greedy top-k.
    """
    logits = jnp.einsum(
        "...d,de->...e", x.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, m.top_k)
    if m.norm_topk_prob:
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-9)
    return gates, topv, topi


def held_pick(topi: jax.Array, m: MoEConfig):
    """(local expert index, held mask) of each pick: the index into this
    device's expert weights, and whether this device holds that expert."""
    local = topi - m.experts_first
    return local, (local >= 0) & (local < m.held)


def route_row(topv: jax.Array, topi: jax.Array, m: MoEConfig, capacity: int):
    """Capacity-route one row of S tokens over the held experts, given the
    router's picks topv/topi (S, k).

    Returns (idx (H, C) int32 — token id per expert slot, S = empty slot;
             w (H, C) f32 — combine weight per slot;
             frac (H,) — fraction of tokens dispatched per expert),
    H the held experts; picks of experts held elsewhere take no slot.
    """
    s = topi.shape[0]
    e = m.held
    local, held = held_pick(topi, m)

    idx_flat = jnp.full((e * capacity + 1,), s, dtype=jnp.int32)
    w_flat = jnp.zeros((e * capacity + 1,), jnp.float32)
    counts = jnp.zeros((e,), jnp.int32)
    token_ids = jnp.arange(s, dtype=jnp.int32)
    for slot in range(m.top_k):
        eidx = local[:, slot]  # (S,)
        # an expert held elsewhere one-hots to a zero row: it takes no slot
        onehot = jax.nn.one_hot(eidx, e, dtype=jnp.int32)  # (S, H)
        pos = jnp.cumsum(onehot, axis=0) - 1 + counts[None, :]  # (S, H)
        counts = counts + jnp.sum(onehot, axis=0)
        pos_tok = jnp.sum(pos * onehot, axis=-1)  # (S,)
        keep = held[:, slot] & (pos_tok < capacity)
        flat = jnp.where(keep, eidx * capacity + pos_tok, e * capacity)
        idx_flat = idx_flat.at[flat].set(token_ids)
        w_flat = w_flat.at[flat].set(topv[:, slot])

    idx = idx_flat[: e * capacity].reshape(e, capacity)
    w = w_flat[: e * capacity].reshape(e, capacity)
    frac = jnp.minimum(counts, capacity).astype(jnp.float32) / max(s, 1)
    return idx, w, frac


def _capacity_experts(p, xc, gates, topv, topi, m: MoEConfig, compute_dtype):
    """Training dispatch: (B, S, D) routed output and the aux loss."""
    b, s, d = xc.shape
    cap = capacity_of(s, m)
    idx, w, frac = jax.vmap(lambda v, i: route_row(v, i, m, cap))(topv, topi)
    # idx, w: (B, H, C); row-local token ids (S = empty)

    # dispatch: row-local gather
    def gather_row(xr, ir):  # (S,D), (H,C) -> (H,C,D)
        return jnp.take(xr, ir, axis=0, mode="fill", fill_value=0)

    xin = jax.vmap(gather_row)(xc, idx)  # (B,H,C,D)
    xin = constrain(xin, "act_batch", "experts_act", None, None)

    g = jnp.einsum("becd,edf->becf", xin, p["w_gate"].astype(compute_dtype))
    u = jnp.einsum("becd,edf->becf", xin, p["w_up"].astype(compute_dtype))
    h = jax.nn.silu(g) * u
    h = constrain(h, "act_batch", "experts_act", None, None)
    eo = tp_out_einsum("becf,efd->becd", h,
                       p["w_down"].astype(compute_dtype), compute_dtype)
    eo = eo * w[..., None].astype(compute_dtype)

    # combine: row-local scatter-add (empty slots dropped)
    def scatter_row(er, ir):  # (H,C,D), (H,C) -> (S,D)
        out = jnp.zeros((s, d), er.dtype)
        return out.at[ir.reshape(-1)].add(
            er.reshape(-1, er.shape[-1]), mode="drop"
        )

    out = jax.vmap(scatter_row)(eo, idx)
    out = constrain(out, "act_batch", None, None)

    # Switch aux loss over the held experts: E * sum_e f_e * mean_gate_e
    mean_gate = jax.lax.dynamic_slice_in_dim(
        jnp.mean(gates, axis=(0, 1)), m.experts_first, m.held
    )
    aux = m.n_experts * jnp.sum(jnp.mean(frac, axis=0) * mean_gate)
    return out, aux


def _grouped_experts(p, xt, topv, topi, m: MoEConfig, compute_dtype):
    """Dropless dispatch of tokens xt (T, D): every (token, held expert)
    pair, sorted by expert, through one grouped product per weight.

    Returns (out (T, D), load (2,) int32: the pairs computed and the
    busiest held expert's pairs)."""
    t, k = topi.shape
    local, held = held_pick(topi, m)
    # picks of experts held elsewhere sort last, past every group
    key = jnp.where(held, local, m.held).reshape(-1)  # (T*k,)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(
        key[:, None] == jnp.arange(m.held, dtype=key.dtype), axis=0,
        dtype=jnp.int32,
    )  # (H,) pairs per held expert
    xs = jnp.take(xt, order // k, axis=0)  # (T*k, D), by expert
    g = jax.lax.ragged_dot(xs, p["w_gate"].astype(compute_dtype), sizes)
    u = jax.lax.ragged_dot(xs, p["w_up"].astype(compute_dtype), sizes)
    h = jax.nn.silu(g) * u
    y = jax.lax.ragged_dot(h, p["w_down"].astype(compute_dtype), sizes)
    # rows past the held pairs belong to no group: the product leaves
    # them undefined, and they carry no weight
    y = jnp.where((jnp.arange(t * k) < jnp.sum(sizes))[:, None], y, 0)
    y = jnp.take(y, jnp.argsort(order), axis=0).reshape(t, k, -1)
    w = jnp.where(held, topv, 0.0)  # (T, k)
    out = jnp.einsum("tk,tkd->td", w, y.astype(jnp.float32))
    load = jnp.stack([jnp.sum(sizes), jnp.max(sizes)])
    return out.astype(compute_dtype), load


@jax.named_scope("moe")
def moe_forward(
    p: dict, x: jax.Array, cfg: ArchConfig, compute_dtype, mode: str = "train"
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux_loss scalar, load (2,) int32).

    ``mode`` "train" routes with a capacity (and computes the aux loss);
    every serving mode is dropless.  ``load`` is the serving dispatch's
    (pairs computed, busiest held expert's pairs), zeros in training."""
    m = cfg.moe
    b, s, d = x.shape
    xc = x.astype(compute_dtype)
    with jax.named_scope("router"):
        gates, topv, topi = route(p, xc, m)
    with jax.named_scope("experts"):
        if mode == "train":
            out, aux = _capacity_experts(p, xc, gates, topv, topi, m,
                                         compute_dtype)
            load = jnp.zeros((2,), jnp.int32)
        else:
            out, load = _grouped_experts(
                p, xc.reshape(b * s, d), topv.reshape(b * s, -1),
                topi.reshape(b * s, -1), m, compute_dtype,
            )
            out = out.reshape(b, s, d)
            aux = jnp.asarray(0.0, jnp.float32)
        if m.n_shared:
            out = out + mlp_forward(p["shared"], xc, compute_dtype)
        if m.dense_residual:
            out = out + mlp_forward(p["dense"], xc, compute_dtype)
    out = jax.ad_checkpoint.checkpoint_name(out, "moe_out")
    return out, aux.astype(jnp.float32), load
