"""The latent-attention + expert family (DeepSeek-V2) against its plain
reference, at a toy size on the CPU: d_model 64, 8 experts of which this
"chip" holds 2, vocabulary 512, pages of 4 tokens.

The served logits are read where the engine samples them (a callback on
the sampler's input), so the comparison covers the whole served path:
prefill into a batch-1 cache, its insert into the page pool, then the
absorbed decode through the paged attention block and the dropless
expert layer."""

import json
import pathlib
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.harness import load_module
from bench_toy import REPO, TOY

TOY_MOE = pathlib.Path(__file__).resolve().parent / "data" / "toy_mla_moe"
CELL = "toy-mla-moe.backlog"
SEED = 2**33 + 21

#: float32 on both sides; the program decodes in the absorbed form with an
#: online softmax over page blocks and sums each token's expert pairs in
#: another order than the reference's expanded, one-pass form, so logits
#: (magnitude about 2) agree to float32 summation, far below the 0.01 or
#: more that a wrong routing weight or a missing expert moves them
LOGIT_TOL = 1e-4


def toy_config(**changes) -> dict:
    config = json.loads((TOY_MOE / "configs/toy-mla-moe.json").read_text())
    config.update(changes)
    return config


def modules():
    return (load_module(REPO / "bench/adapters/mla_moe.py", "a_mla_moe"),
            load_module(REPO / "bench/reference/mla_moe.py", "r_mla_moe"))


def served_logits(monkeypatch, config, prompts, max_new, *, n_slots=1,
                  cfg=None, params=None):
    """Serve ``prompts`` through ``ServeEngine`` (greedy); returns the
    served tokens of each request and every logits array the engine
    sampled from, in call order: (1, V) per prefill, (n_slots, V) per
    decode step."""
    from repro.serve import Request
    from repro.serve import engine as engine_mod

    adapter, ref = modules()
    seen = []
    sample = engine_mod.sample_tokens

    def spy(logits, *rest):
        jax.debug.callback(lambda lg: seen.append(np.asarray(lg)), logits,
                           ordered=True)
        return sample(logits, *rest)

    monkeypatch.setattr(engine_mod, "sample_tokens", spy)
    cfg = cfg or adapter.arch_config(config)
    if params is None:
        w = weights.make(ref.layout(config), SEED, config["weights"],
                         config["dtypes"]["param"])
        params = adapter.program_params(w, cfg)
    eng = dict(config["engine"], n_slots=n_slots)
    engine = adapter.engine(dict(config, engine=eng), cfg, params, 0)
    rids = [engine.submit(Request(p, max_new_tokens=max_new)) for p in prompts]
    done = {c.request_id: c for c in engine.run_until_idle()}
    jax.effects_barrier()
    return [list(done[r].tokens) for r in rids], seen, engine


def reference_logits(config, prompt, served):
    """The reference's logits at the positions that produced ``served``."""
    _, ref = modules()
    w = weights.make(ref.layout(config), SEED, config["weights"], "float32")
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    h = ref.hidden(w, jnp.asarray(seq), ref.static(config))
    lg = np.asarray(h @ ref.head_matrix(w, config))
    p = len(prompt)
    return lg[p - 1 : p - 1 + len(served)]


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


def program_rows(seen, n):
    """One slot's logits: the prefill's row, then row 0 of each decode."""
    return np.stack([seen[0][0]] + [s[0] for s in seen[1:n]])


def test_reference_equals_program_forward_in_float32():
    from repro.models import lm

    config = toy_config()
    adapter, ref = modules()
    cfg = adapter.arch_config(config)
    w = weights.make(ref.layout(config), 11, config["weights"])
    tokens = prompt_of(40)
    with jax.default_matmul_precision("highest"):
        want, _, _ = lm.forward(adapter.program_params(w, cfg),
                                {"tokens": jnp.asarray(tokens[None])}, cfg,
                                "prefill")
    h = ref.hidden(w, jnp.asarray(tokens), ref.static(config))
    np.testing.assert_allclose(
        np.asarray(h @ ref.head_matrix(w, config)),
        np.asarray(want[0, :, : config["vocab_size"]]), rtol=1e-5, atol=1e-5,
    )


def _renormalised_top_k(config, cfg, params):
    import dataclasses

    moe = dataclasses.replace(cfg.moe, norm_topk_prob=True)
    return dataclasses.replace(cfg, moe=moe), params


def _dropped_shared_expert(config, cfg, params):
    moe = params["blocks"]["g1_a"]["moe"]
    moe["shared"] = jax.tree.map(jnp.zeros_like, moe["shared"])
    return cfg, params


@pytest.mark.parametrize("fault", [None, _renormalised_top_k,
                                   _dropped_shared_expert])
def test_prefill_then_paged_decode_matches_reference(monkeypatch, fault):
    """Served logits (prefill, then decode through the paged latent cache)
    against the reference's full forward pass over the same tokens; a
    planted fault in the routing or the shared experts fails it."""
    config = toy_config()
    adapter, ref = modules()
    cfg, params = adapter.arch_config(config), None
    if fault is not None:
        w = weights.make(ref.layout(config), SEED, config["weights"])
        cfg, params = fault(config, cfg, adapter.program_params(w, cfg))
    prompt = prompt_of(37)
    (served,), seen, _ = served_logits(monkeypatch, config, [prompt], 20,
                                       cfg=cfg, params=params)
    got = program_rows(seen, len(served))[:, : config["vocab_size"]]
    want = reference_logits(config, prompt, served)
    err = np.abs(got - want).max()
    if fault is None:
        assert err < LOGIT_TOL
    else:
        assert err > 100 * LOGIT_TOL


def test_expert_shares_add_up_to_the_uncut_layer():
    """Each of four devices holds 2 of the 8 experts: the routed parts of
    the four shares, with the shared experts counted once, equal the
    reference's expert layer that holds all 8."""
    import dataclasses

    from repro.configs.base import MoEConfig
    from repro.models.moe import moe_forward

    config = toy_config(n_routed_experts=8, experts_first=0, published={})
    adapter, ref = modules()
    cfg = adapter.arch_config(config)
    w = weights.make(ref.layout(config), SEED, config["weights"])
    lw = {k: w[k][0] for k in ("router", "e_gate", "e_up", "e_down",
                               "s_gate", "s_up", "s_down")}
    y = jax.random.normal(jax.random.PRNGKey(0), (24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(y, lw, ref.static(config))
        total = 0.0
        for share in range(4):
            held = slice(2 * share, 2 * share + 2)
            moe = MoEConfig(n_experts=8, top_k=3, d_expert=32, n_shared=1,
                            norm_topk_prob=False, experts_first=2 * share,
                            n_held=2)
            shared = {"gate": lw["s_gate"], "up": lw["s_up"],
                      "down": lw["s_down"]}
            if share:  # every device computes the shared experts alike
                shared = jax.tree.map(jnp.zeros_like, shared)
            p = {"router": lw["router"], "w_gate": lw["e_gate"][held],
                 "w_up": lw["e_up"][held], "w_down": lw["e_down"][held],
                 "shared": shared}
            out, _, _ = moe_forward(p, y[None], dataclasses.replace(cfg, moe=moe),
                                    jnp.float32, mode="prefill")
            total = total + out[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_dropless_alone_equals_in_a_full_batch(monkeypatch):
    """One request's logits served alone equal its logits in a full batch
    of four (bfloat16 compute): no token's experts depend on what else is
    in the batch.  Both runs compile the same programs, so the only room
    allowed is one bfloat16 rounding of the logits."""
    config = toy_config(dtypes={"param": "float32", "compute": "bfloat16"})
    a = prompt_of(29, seed=1)
    others = [prompt_of(n, seed=2 + n) for n in (40, 23, 35)]
    (alone, *_), seen_alone, _ = served_logits(monkeypatch, config, [a], 12,
                                               n_slots=4)
    (batched, *_), seen_batch, _ = served_logits(
        monkeypatch, config, [a] + others, 12, n_slots=4)
    assert alone == batched
    got_alone = np.stack([seen_alone[0][0]] + [
        s[0] for s in seen_alone if s.shape[0] == 4][: len(alone) - 1])
    got_batch = np.stack([seen_batch[0][0]] + [
        s[0] for s in seen_batch if s.shape[0] == 4][: len(batched) - 1])
    scale = np.abs(got_alone).max()
    np.testing.assert_allclose(got_batch.astype(np.float32),
                               got_alone.astype(np.float32),
                               atol=scale * 2.0 ** -8)


def test_expert_load_counters_count_the_reference_routes(monkeypatch):
    """``serve_moe_pairs_total`` counts the (token, held expert) pairs of
    every decode step and MoE layer, ``serve_moe_busiest_total`` the
    busiest held expert's pairs per step and layer: with one slot, the
    picks of each decoded position that fall on the held experts, as the
    reference routes them."""
    config = toy_config()
    _, ref = modules()
    prompt = prompt_of(33, seed=5)
    (served,), _, engine = served_logits(monkeypatch, config, [prompt], 24)
    w = weights.make(ref.layout(config), SEED, config["weights"])
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    picks = np.asarray(ref.routes(w, jnp.asarray(seq), ref.static(config)))
    first, held = config["experts_first"], config["n_routed_experts"]
    decoded = picks[:, len(prompt) :]  # positions the decode steps fed
    on_held = (decoded >= first) & (decoded < first + held)
    pairs = engine.registry.get("serve_moe_pairs_total").value
    busiest = engine.registry.get("serve_moe_busiest_total").value
    assert on_held.sum() > 0
    assert pairs == on_held.sum()
    assert busiest == on_held.any(axis=-1).sum()


def test_toy_cell_runs_through_the_harness(tmp_path):
    """A toy cell of the family, added as new files in a copy of the
    benchmark, found by name and run; the program passes and the control
    (the reference in bfloat16, judged by the same limit) does not."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(TOY_MOE / "configs/toy-mla-moe.json",
                tmp_path / "bench/configs/toy-mla-moe.json")
    shutil.copy(TOY / "traffic/toy-backlog.json",
                tmp_path / "bench/traffic/toy-backlog.json")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": CELL, "config": "toy-mla-moe",
                               "traffic": "toy-backlog", "chips": 1,
                               "why": "CPU tests"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    result = harness.run(tmp_path, CELL, SEED, 1.0, False,
                         t_start=time.perf_counter(), require_tpu=False,
                         control=True)
    assert result["correct"] is True
    assert result["control"]["correct"] is False
    assert result["checks"]["window_compiles"]["value"] == 0
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"output_tok_s", "itl_p95_ms", "setup_s"}


@pytest.mark.parametrize("name", ["deepseek-v2-lite", "toy-mla-moe"])
def test_layout_and_work_model_match_the_program(name):
    """The reference's layout, handed to the program by the adapter, is
    the program's own parameter tree, leaf for leaf; the work model's
    weight bytes are that layout's, less the embedding table and the
    head's vocabulary padding; DeepSeek-V2-Lite's latent cache holds
    27 × (512 + 64) bfloat16 values a token."""
    import math

    from bench import work_mla_moe
    from repro.models import lm, params as pm

    path = REPO / "bench/configs" / f"{name}.json"
    config = (json.loads(path.read_text()) if path.exists()
              else toy_config())
    adapter, ref = modules()
    cfg = adapter.arch_config(config)
    layout = ref.layout(config)
    ours = adapter.program_params(
        {k: jax.ShapeDtypeStruct(s, "float32") for k, (s, _) in layout.items()},
        cfg)
    want = pm.abstract_params(lm.build_metas(cfg))
    assert jax.tree.structure(ours) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(want)):
        assert a.shape == b.shape
    d, v = config["hidden_size"], config["vocab_size"]
    vp = layout["embed"][0][0]
    held = sum(math.prod(s) for s, _ in layout.values()) - vp * d - (vp - v) * d
    pb = {"float32": 4, "bfloat16": 2}[config["dtypes"]["param"]]
    assert work_mla_moe.weight_bytes(config) == held * pb
    if name == "deepseek-v2-lite":
        assert work_mla_moe.latent_bytes_per_token(config) == 31_104
        assert work_mla_moe.held_pairs_per_token(config) == 0.75
