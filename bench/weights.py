"""Seeded weights, made on the device in one jitted call.

Both sides draw from here: the harness (which hands the arrays to the
program under test) and the plain reference (which regenerates them after
the program's state is freed).  A leaf's values depend only on the seed,
the leaf's name and the layout, never on the program.
"""

from __future__ import annotations

import numpy as np


def seed_words(seed: int, stream: str) -> np.ndarray:
    """Two uint32 words from any whole-number ``seed`` and a stream name:
    seeds beyond 32 bits stay distinct, and each stream is independent."""
    tag = int.from_bytes(stream.encode(), "little") % (1 << 63)
    return np.random.SeedSequence([seed % (1 << 64), tag]).generate_state(
        2, np.uint32
    )


def rng(seed: int, stream: str) -> np.random.Generator:
    """Host-side generator for one named stream of one seed."""
    return np.random.default_rng(seed_words(seed, stream))


def make(layout: dict, seed: int, spec: dict, dtype: str = "float32") -> dict:
    """``layout`` maps a leaf name to ``(shape, init)``: ``"normal"`` draws
    N(0, spec["std"]²), ``"norm"`` draws N(1, spec["norm_std"]²) (norm
    gains).  Returns a dict of device arrays in ``dtype``."""
    import jax
    import jax.numpy as jnp

    names = sorted(layout)
    std, norm_std = float(spec["std"]), float(spec["norm_std"])

    @jax.jit
    def generate(key):
        out = {}
        for i, name in enumerate(names):
            shape, init = layout[name]
            z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            if init == "norm":
                v = 1.0 + norm_std * z
            elif init == "normal":
                v = std * z
            else:
                raise ValueError(f"unknown init {init!r} for {name}")
            out[name] = v.astype(dtype)
        return out

    key = jax.random.wrap_key_data(
        jnp.asarray(seed_words(seed, "weights"), jnp.uint32)
    )
    return generate(key)
