"""The traffic generator and its arrival kinds."""

import collections
import json

import numpy as np
import pytest

from bench import traffic
from bench_toy import REPO

MIXES = {"chat-backlog": "stablelm-1.6b", "rag": "granite-3-8b"}
BIG_SEED = 2**33 + 12345


def config_of(mix_name):
    return json.loads(
        (REPO / "bench/configs" / f"{MIXES[mix_name]}.json").read_text()
    )


def slots_of(mix_name):
    return config_of(mix_name)["engine"]["n_slots"]


@pytest.mark.parametrize("mix_name", sorted(MIXES))
def test_same_seed_same_schedule(mix_name):
    mix = traffic.load_mix(mix_name)
    a = traffic.generate(mix, 1000, BIG_SEED)
    b = traffic.generate(mix, 1000, BIG_SEED)
    c = traffic.generate(mix, 1000, BIG_SEED + 1)
    assert [(p.due_s, p.max_new_tokens) for p in a] == [
        (p.due_s, p.max_new_tokens) for p in b
    ]
    assert all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))
    assert any(not np.array_equal(p.prompt, q.prompt) for p, q in zip(a, c))


@pytest.mark.parametrize("mix_name", sorted(MIXES))
def test_seeds_share_the_work(mix_name):
    """Two seeds serve one multiset of lengths (and of gaps) per block."""
    mix = traffic.load_mix(mix_name)
    runs = [traffic.generate(mix, 1000, s, slots=slots_of(mix_name))
            for s in (1, BIG_SEED)]
    for attr in ("max_new_tokens", "prompt"):
        counts = [
            collections.Counter(
                len(p.prompt) if attr == "prompt" else p.max_new_tokens
                for p in run
            )
            for run in runs
        ]
        assert counts[0] == counts[1]
    gaps = [np.sort(np.diff([0.0] + [p.due_s for p in run])) for run in runs]
    np.testing.assert_allclose(gaps[0], gaps[1])


@pytest.mark.parametrize("mix_name", sorted(MIXES))
def test_lengths_clip_and_fit(mix_name):
    mix = traffic.load_mix(mix_name)
    config = config_of(mix_name)
    sched = traffic.generate(mix, config["vocab_size"], 7,
                             slots=config["engine"]["n_slots"])
    prompts = np.array([len(p.prompt) for p in sched])
    outputs = np.array([p.max_new_tokens for p in sched])
    assert prompts.min() >= mix["prompt"]["min"]
    assert (prompts + outputs).max() <= traffic.context_bound(mix)
    assert outputs.min() >= 1
    assert outputs.max() <= mix["output"]["max"]
    assert traffic.context_bound(mix) <= config["engine"]["max_len"]
    assert (prompts + outputs).max() <= config["engine"]["max_len"]
    assert max(int(p.prompt.max()) for p in sched) < config["vocab_size"]
    # past the requests in flight at the start, the lengths keep to
    # their bounds and the medians sit near the stated ones
    fresh = slice(len(sched) - traffic.n_requests(mix), None)
    assert prompts[fresh].max() <= mix["prompt"]["max"]
    assert outputs[fresh].min() >= mix["output"]["min"]
    assert abs(np.median(prompts[fresh]) / mix["prompt"]["median"] - 1) < 0.05
    assert abs(np.median(outputs[fresh]) / mix["output"]["median"] - 1) < 0.05


def test_backlog_is_due_at_once():
    mix = traffic.load_mix("chat-backlog")
    slots = slots_of("chat-backlog")
    sched = traffic.generate(mix, 1000, 3, slots=slots)
    assert len(sched) == slots + mix["requests"]
    assert all(p.due_s == 0.0 for p in sched)


def test_backlog_starts_in_its_stationary_state():
    """The first requests stand for those a long-running engine holds:
    each part way through a length-biased output, so together they hold
    the mix's mean resident context, the same set for every seed."""
    mix = traffic.load_mix("chat-backlog")
    slots = 64
    runs = [traffic.generate(mix, 1000, s, slots=slots)[:slots]
            for s in (5, BIG_SEED)]
    sets = [sorted((len(p.prompt), p.max_new_tokens) for p in run)
            for run in runs]
    assert sets[0] == sets[1]
    assert [len(p.prompt) for p in runs[0]] != [len(p.prompt) for p in runs[1]]
    held = np.mean([p for p, _ in sets[0]])
    assert abs(held / traffic.mean_resident(mix) - 1) < 0.1
    # length-biased: what is left to serve is longer than a fresh
    # request's median output
    assert np.median([o for _, o in sets[0]]) > mix["output"]["median"] / 2
    assert min(o for _, o in sets[0]) >= 1


def test_open_loop_starts_empty():
    mix = traffic.load_mix("rag")
    sched = traffic.generate(mix, 1000, 3, slots=slots_of("rag"))
    assert len(sched) == traffic.n_requests(mix)
    assert min(p.due_s for p in sched) > 0


def test_poisson_rate_and_order():
    mix = traffic.load_mix("rag")
    sched = traffic.generate(mix, 1000, BIG_SEED)
    due = np.array([p.due_s for p in sched])
    assert np.all(np.diff(due) >= 0) and due[0] > 0
    n = len(due)
    assert n == traffic.n_requests(mix) and n % mix["block"] == 0
    # whole blocks hold the stratified gaps, each its stratum's mean, so
    # the mean gap is 1/rate
    assert abs(due[-1] / n * mix["rate_per_s"] - 1) < 1e-9


def test_quantile_grid_clips():
    grid = traffic.lognormal_grid(
        {"median": 100, "sigma": 2.0, "min": 50, "max": 200}, 64
    )
    assert grid.min() == 50 and grid.max() == 200 and len(grid) == 64
