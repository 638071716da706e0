"""The serving side of the latent-attention + expert family: YaRN rope,
the expert layer's dispatch in a compiled decode step, and its scopes."""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import YarnScaling
from repro.models import layers

#: DeepSeek-V2-Lite's rope_scaling and rope width
V2_LITE = YarnScaling(factor=40, original_max_position_embeddings=4096,
                      beta_fast=32, beta_slow=1, mscale=0.707,
                      mscale_all_dim=0.707)


def test_yarn_at_deepseek_v2_lite():
    """Ramp from pair 10 to pair 23 of 32; below it the published
    frequencies, above it those divided by 40; M = 0.1 * 0.707 * ln 40 + 1."""
    assert layers.yarn_range(64, 10000.0, V2_LITE) == (10, 23)
    f = np.asarray(layers.rope_inv_freq(64, 10000.0, V2_LITE))
    extra = np.asarray(layers.rope_inv_freq(64, 10000.0))
    i = np.arange(32)
    np.testing.assert_allclose(extra, 10000.0 ** (-2 * i / 64), rtol=1e-6)
    np.testing.assert_array_equal(f[:11], extra[:11])
    np.testing.assert_array_equal(f[23:], extra[23:] / 40)
    assert np.all(f[11:23] < extra[11:23]) and np.all(f[11:23] > extra[11:23] / 40)
    m = layers.yarn_mscale(40, 0.707)
    assert abs(m - (0.1 * 0.707 * math.log(40) + 1)) < 1e-12
    assert abs(m - 1.2608) < 1e-4


def test_rope_without_scaling_is_unchanged():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 2, 8))
    pos = jnp.arange(5)[None]
    unit = YarnScaling(factor=1.0, original_max_position_embeddings=4096)
    np.testing.assert_allclose(np.asarray(layers.rope(x, pos, 10000.0, unit)),
                               np.asarray(layers.rope(x, pos, 10000.0)),
                               rtol=1e-6)


def _held_decode_text(slots):
    """The compiled CPU decode program of a reduced DeepSeek-V2 holding 2
    of 8 experts, paged."""
    from repro.serve import Request, ServeEngine

    cfg = get_config("deepseek-v2-236b").reduced()
    moe = dataclasses.replace(cfg.moe, n_experts=8, top_k=3, n_held=2,
                              experts_first=4, norm_topk_prob=False)
    cfg = dataclasses.replace(cfg, moe=moe, rope_scaling=V2_LITE)
    engine = ServeEngine(cfg, n_slots=slots, max_len=32, page_size=4)
    engine.submit(Request([1, 2, 3], max_new_tokens=3))
    engine.run_until_idle()
    (text,) = engine.programs.compiled_texts()["jit_decode_fn"]
    return cfg, text


def test_decode_builds_no_capacity_table_and_scopes_the_layer():
    """The compiled decode step has no (B, experts, capacity, D) dispatch
    tensor, and puts the layer's instructions in the ``moe`` scope's
    ``router`` and ``experts`` (the grouped products lower to dots here;
    ``test_tpu_compile.py`` finds the chip's ragged kernel)."""
    from repro.obs import op_scopes

    cfg, text = _held_decode_text(slots=5)
    d = cfg.d_model
    # 5 slots: no weight leads with 5 (the expert layers are 3)
    assert not re.search(rf"\[5,(2|8),\d+,{d}\]", text)
    scopes = set(op_scopes(text, ["moe", "router", "experts"]).values())
    assert {"router", "experts"} <= scopes
