"""Flash attention (forward) — VMEM-tiled online-softmax fused attention.

Grid (batch, q_head, q_blocks, kv_blocks), kv innermost so the running
(max, denom, acc) state stays in VMEM scratch across the kv sweep.  GQA is
handled in the BlockSpec index maps: the k/v block index uses
``q_head // group`` so no head replication is materialised in HBM.

Causal masking is applied inside the kernel with iota comparisons; fully
masked kv blocks skip their compute (the DMA still runs — block skipping via
a sparsity map is a §Perf follow-up, not needed for correctness).

Baseline block sizes 128x128: q/k/v/acc tiles at head_dim 128 are 64 KiB
each in f32 — comfortably double-buffered in ~16 MiB VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, block_q: int, block_kv: int, kv_steps: int
):
    j = pl.program_id(3)
    i = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # With causal masking, blocks strictly above the diagonal contribute
    # nothing: skip their FLOPs.
    needed = (not causal) or (j * block_kv <= (i + 1) * block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bkv, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_idx = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0
            )
            kv_idx = j * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1
            )
            s = jnp.where(q_idx >= kv_idx, s, _NEG_INF)
        m_prev = m_ref[...]  # (bq, 128) broadcast lanes
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)  # (bq, 128)
        p = jnp.exp(s - m_new[:, :1])  # (bq, bkv)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 128)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(j == kv_steps - 1)
    def _flush():
        denom = l_ref[...][:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_kv", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, KH, Skv, D)
    v: jax.Array,  # (B, KH, Skv, D)
    *,
    causal: bool = True,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, h, sq, d = q.shape
    _, kh, skv, _ = k.shape
    dv = v.shape[-1]
    if h % kh:
        raise ValueError(f"q heads {h} not a multiple of kv heads {kh}")
    group = h // kh
    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    if sq % bq or skv % bkv:
        raise ValueError("sequence lengths must tile by block sizes")
    grid = (b, h, sq // bq, skv // bkv)
    scale = 1.0 / (d ** 0.5)
    return pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale,
            causal=causal,
            block_q=bq,
            block_kv=bkv,
            kv_steps=grid[3],
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec(
                (1, 1, bkv, d), lambda b_, h_, i, j: (b_, h_ // group, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, bkv, dv), lambda b_, h_, i, j: (b_, h_ // group, j, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, dv), lambda b_, h_, i, j: (b_, h_, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
