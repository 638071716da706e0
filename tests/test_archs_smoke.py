"""Per-architecture smoke tests: reduced config, one forward + train step on
CPU, asserting output shapes and finite values (assignment requirement)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.launch.steps import TrainHyper, make_train_step
from repro.models import lm
from repro.optim.adamw import AdamW

B, S = 2, 32


def _batch(cfg, rng):
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    if cfg.frontend == "patch_embed":
        return {
            "embeds": jnp.asarray(
                rng.standard_normal((B, S, cfg.d_model)), jnp.bfloat16
            ),
            "labels": labels,
        }
    return {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
        "labels": labels,
    }


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_forward_shapes_and_finiteness(arch, rng):
    cfg = get_config(arch).reduced()
    params = lm.init_params(cfg, seed=0)
    batch = _batch(cfg, rng)
    logits, aux, _ = lm.forward(params, batch, cfg, mode="train")
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_one_train_step(arch, rng):
    cfg = get_config(arch).reduced()
    opt = AdamW(moment_dtype=cfg.opt_dtype)
    step = jax.jit(make_train_step(cfg, opt, TrainHyper(total_steps=10)))
    params = lm.init_params(cfg, seed=0)
    opt_state = opt.init(params)
    batch = _batch(cfg, rng)
    params2, opt_state2, metrics = step(params, opt_state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert int(opt_state2.step) == 1
    # parameters actually moved (warmup LR is tiny: check exact inequality)
    moved = any(
        not np.array_equal(np.asarray(b, np.float32), np.asarray(a, np.float32))
        for b, a in zip(jax.tree.leaves(params), jax.tree.leaves(params2))
    )
    assert moved


@pytest.mark.parametrize(
    "arch", ["llama3.2-1b", "deepseek-v2-236b", "mamba2-2.7b", "zamba2-7b"]
)
def test_prefill_decode_consistency(arch, rng):
    """Decode against the cache must agree with full-sequence forward."""
    cfg = dataclasses.replace(
        get_config(arch).reduced(), compute_dtype="float32", remat="none"
    )
    params = lm.init_params(cfg, seed=0)
    s, maxlen = 16, 32
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, s + 1)), jnp.int32)
    # training routes experts with a per-row capacity and drops overflow
    # tokens, so a drop there depends on S; serving is dropless, and its
    # full-sequence forward (prefill mode, no cache) is the reference
    full_mode = "prefill" if cfg.moe else "train"
    logits_full, _, _ = lm.forward(params, {"tokens": toks}, cfg, mode=full_mode)
    cache = lm.init_cache(cfg, B, maxlen)
    logits_pre, cache = lm.prefill(params, {"tokens": toks[:, :s]}, cfg, cache)
    logits_dec, cache, _ = lm.decode_step(params, toks[:, s : s + 1], cfg, cache)
    tol = 1e-3  # float32 throughout: cache vs full-sequence summation order
    np.testing.assert_allclose(
        np.asarray(logits_dec[:, 0]), np.asarray(logits_full[:, s]),
        rtol=tol, atol=tol,
    )
    assert np.all(np.asarray(cache["index"]) == s + 1)  # per-slot positions


def test_musicgen_vocab_is_encodec_sized():
    cfg = get_config("musicgen-large")
    assert cfg.vocab_size == 2048


def test_param_counts_match_billing():
    # sanity: computed param counts are in the advertised ballpark
    expect = {
        "arctic-480b": (430e9, 520e9),
        "deepseek-v2-236b": (210e9, 260e9),
        "command-r-35b": (28e9, 40e9),
        "mamba2-2.7b": (2.4e9, 3.2e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo < n < hi, (arch, n)
