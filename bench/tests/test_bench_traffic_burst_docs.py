"""The bursty (Gamma) arrival kind and the long-document backlog mix."""

import json

import numpy as np
import pytest

from bench import traffic
from bench_toy import REPO

BIG_SEED = 2**33 + 777


def test_gamma_block_mean_gap_is_one_over_rate():
    """Every block holds the means of the Gamma's equal-probability
    strata, so each block's mean gap is exactly 1/rate; the gaps keep the
    shape's spread (coefficient of variation near 1/sqrt(0.25) = 2)."""
    mix = traffic.load_mix("burst")
    sched = traffic.generate(mix, 1000, BIG_SEED)
    due = np.array([p.due_s for p in sched])
    gaps = np.diff(np.concatenate([[0.0], due]))
    block = mix["block"]
    assert len(gaps) % block == 0
    means = gaps.reshape(-1, block).mean(axis=1)
    np.testing.assert_allclose(means * mix["rate_per_s"], 1.0, rtol=1e-12)
    kind = traffic.load_kind("gamma")
    grid = kind.stratum_means(mix["gamma_shape"], block)
    assert abs(grid.mean() - 1.0) < 1e-12
    assert 1.7 < grid.std() / grid.mean() < 2.0
    assert np.all(np.diff(grid) > 0) and grid[0] > 0


@pytest.mark.parametrize("shape", [0.25, 1.0, 4.0])
def test_gamma_strata_approach_the_distribution(shape):
    """With many strata the stratum means carry the Gamma's own spread."""
    kind = traffic.load_kind("gamma")
    grid = kind.stratum_means(shape, 4096)
    assert abs(grid.mean() - 1.0) < 1e-9
    assert abs(grid.std() / (1 / np.sqrt(shape)) - 1) < 0.05


def test_burst_and_doc_backlog_fit_their_engines():
    for mix_name, config_name in (("burst", "stablelm-1.6b"),
                                  ("doc-backlog", "deepseek-v2-lite")):
        mix = traffic.load_mix(mix_name)
        config = json.loads(
            (REPO / "bench/configs" / f"{config_name}.json").read_text())
        eng = config["engine"]
        sched = traffic.generate(mix, config["vocab_size"], 9,
                                 slots=eng["n_slots"])
        lengths = np.array([len(p.prompt) + p.max_new_tokens for p in sched])
        assert lengths.max() <= traffic.context_bound(mix) <= eng["max_len"]
        assert max(int(p.prompt.max()) for p in sched) < config["vocab_size"]


def test_doc_backlog_holds_long_contexts():
    mix = traffic.load_mix("doc-backlog")
    assert 2400 < traffic.mean_resident(mix) < 2900
    assert traffic.context_bound(mix) == 8192
