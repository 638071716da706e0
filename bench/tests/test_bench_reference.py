"""The plain reference against the program's own forward pass, both in
float32, on seeded weights at a toy size: the two write down the same
equations, independently."""

import json

import jax
import jax.numpy as jnp
import numpy as np

from bench import correct, weights
from bench.harness import load_module
from bench_toy import REPO, TOY


def test_reference_equals_program_in_float32():
    from repro.models import lm

    config = json.loads((TOY / "configs/toy.json").read_text())
    config["dtypes"] = {"param": "float32", "compute": "float32"}
    adapter = load_module(REPO / "bench/adapters/dense.py", "a_dense")
    ref = load_module(REPO / "bench/reference/dense.py", "r_dense")
    cfg = adapter.arch_config(config)
    w = weights.make(ref.layout(config), 11, config["weights"])
    tokens = np.random.default_rng(0).integers(0, config["vocab_size"], 24)
    with jax.default_matmul_precision("highest"):
        want, _, _ = lm.forward(
            adapter.program_params(w, cfg), {"tokens": jnp.asarray(tokens[None])},
            cfg, "train",
        )
    h = ref.hidden(w, jnp.asarray(tokens, jnp.int32), ref.static(config))
    got = h @ ref.head_matrix(w, config)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want[0, :, : config["vocab_size"]]),
        rtol=2e-4, atol=2e-4,
    )


def test_weights_depend_on_seed_only():
    layout = {"a": ((3, 4), "normal"), "g": ((4,), "norm")}
    spec = {"std": 0.02, "norm_std": 0.1}
    a = weights.make(layout, 2**40 + 1, spec)
    b = weights.make(layout, 2**40 + 1, spec)
    c = weights.make(layout, 1, spec)
    assert np.array_equal(a["a"], b["a"]) and not np.array_equal(a["a"], c["a"])
    assert abs(float(jnp.mean(a["g"])) - 1.0) < 0.2


def test_no_finished_request_gives_no_number():
    """A window that finished no request compares nothing: the harness
    then reads no number against the limits and the run is not correct."""
    config = json.loads((TOY / "configs/toy.json").read_text())
    ref = load_module(REPO / "bench/reference/dense.py", "r_dense")
    assert correct.gaps(ref, config, 1, [], 128) == {"tokens_compared": 0}
