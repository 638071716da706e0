"""Pluggable token sampling, fused into the jitted serving steps.

The serving engine never ships logits to the host: the (B, V) logits tensor
stays on device and :func:`sample_tokens` reduces it to (B,) token ids
*inside* the jitted prefill/decode programs, so the per-step host transfer
is token ids only (the decode loop's classic sync bottleneck).

One program covers every sampler: the per-slot knobs — ``temperature`` and
``top_k`` — are *dynamic* (B,) inputs, not trace-time constants, so a batch
can mix a greedy request with a top-k request without retracing.  Greedy is
``temperature == 0``; ``top_k == 0`` disables the top-k filter.

Determinism: each slot's PRNG key is derived from (request seed, token
index) alone — never from the slot number, the engine step, or which other
requests share the batch — so a request replayed under a different batch
composition samples the identical token sequence.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class Sampler:
    """Sampling policy: greedy / temperature / top-k.

    ``kind`` exists for readability; the engine lowers every policy to the
    (temperature, top_k) pair consumed by :func:`sample_tokens`.
    """

    kind: str = "greedy"  # "greedy" | "temperature" | "top_k"
    temperature: float = 0.0
    top_k: int = 0

    def __post_init__(self) -> None:
        if self.kind not in ("greedy", "temperature", "top_k"):
            raise ValueError(
                f"unknown sampler kind '{self.kind}'; "
                "known: greedy, temperature, top_k"
            )
        if self.kind == "greedy" and self.temperature:
            raise ValueError("greedy sampling takes no temperature")
        if self.kind != "greedy" and self.temperature <= 0:
            raise ValueError(f"{self.kind} sampling needs temperature > 0")
        if self.kind == "top_k" and self.top_k < 1:
            raise ValueError("top_k sampling needs top_k >= 1")
        if self.kind != "top_k" and self.top_k:
            raise ValueError(f"{self.kind} sampling takes no top_k")

    # -- constructors --------------------------------------------------------
    @classmethod
    def greedy(cls) -> "Sampler":
        return cls("greedy")

    @classmethod
    def with_temperature(cls, temperature: float) -> "Sampler":
        return cls("temperature", temperature=temperature)

    @classmethod
    def with_top_k(cls, top_k: int, temperature: float = 1.0) -> "Sampler":
        return cls("top_k", temperature=temperature, top_k=top_k)

    @classmethod
    def parse(cls, spec: str) -> "Sampler":
        """CLI spelling: ``greedy`` | ``temperature:0.8`` | ``top_k:40:0.8``."""
        parts = spec.split(":")
        if parts == ["greedy"]:
            return cls.greedy()
        if parts[0] == "temperature" and len(parts) == 2:
            return cls.with_temperature(float(parts[1]))
        if parts[0] in ("top_k", "top-k") and len(parts) in (2, 3):
            t = float(parts[2]) if len(parts) > 2 else 1.0
            return cls.with_top_k(int(parts[1]), t)
        raise ValueError(f"unknown sampler spec '{spec}'")

    # -- lowering ------------------------------------------------------------
    @property
    def knobs(self) -> tuple[float, int]:
        """The dynamic (temperature, top_k) pair for :func:`sample_tokens`."""
        return (float(self.temperature), int(self.top_k))


def _slot_key(seed: jax.Array, step: jax.Array) -> jax.Array:
    base = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(base, seed), step)


@jax.named_scope("sample")
def sample_tokens(
    logits: jax.Array,  # (B, V) float
    seeds: jax.Array,  # (B,) int32: per-request sampling seed
    steps: jax.Array,  # (B,) int32: per-request token index
    temperatures: jax.Array,  # (B,) float32: 0 = greedy
    top_ks: jax.Array,  # (B,) int32: 0 = no top-k filter
) -> jax.Array:
    """(B,) sampled token ids — trace-time shape-stable for any policy mix.

    The expensive paths are gated on *runtime* batch predicates
    (``lax.cond``), so an all-greedy batch — the serving default — skips
    both the O(V log V) top-k threshold sort and the categorical draw
    entirely without needing a separate trace.
    """
    v = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    greedy = jnp.argmax(lf, axis=-1).astype(jnp.int32)

    def topk_filter() -> jax.Array:
        # top-k with *dynamic* per-row k: threshold at the k-th largest
        # logit (a sort, not lax.top_k, because k is not a trace constant)
        sorted_desc = -jnp.sort(-lf, axis=-1)
        kth = jnp.clip(top_ks - 1, 0, v - 1)
        thresh = jnp.take_along_axis(sorted_desc, kth[:, None], axis=-1)
        return jnp.where((top_ks[:, None] > 0) & (lf < thresh), _NEG, lf)

    def draw() -> jax.Array:
        filtered = jax.lax.cond(jnp.any(top_ks > 0), topk_filter, lambda: lf)
        temps = jnp.maximum(temperatures, 1e-6)[:, None]
        keys = jax.vmap(_slot_key)(seeds, steps)
        sampled = jax.vmap(jax.random.categorical)(keys, filtered / temps)
        return jnp.where(
            temperatures <= 0, greedy, sampled.astype(jnp.int32)
        )

    return jax.lax.cond(
        jnp.any(temperatures > 0), draw, lambda: greedy
    )
