"""One general generator for every traffic mix.

A mix is ``traffic/<mix>.json``.  Its ``kind`` names the arrival module
``traffic_kinds/<kind>.py`` (a function ``arrivals(mix, n, rng)`` giving
due times in seconds from the start of the lead-in).  Lengths are
lognormal (``median``, ``sigma``, clipped to ``[min, max]``), drawn by
stratified blocks: every block of ``block`` requests holds the same
multiset of lengths (the quantile grid of the distribution) in an order
drawn from the seed.  Two seeds therefore serve the same work in another
order, which keeps a cell's spread from being the seed's doing.

A kind whose module also defines ``in_flight(mix, slots, rng)`` starts
its run in its stationary state: the requests that function returns are
due first and stand for the ones a long-running engine already holds
(see :func:`stationary`).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
from statistics import NormalDist

import numpy as np

from bench import weights

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request of the schedule."""

    due_s: float  # seconds after the lead-in starts
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int


def load_mix(name: str, root: pathlib.Path = HERE) -> dict:
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def load_kind(kind: str, root: pathlib.Path = HERE):
    path = root / "traffic_kinds" / f"{kind}.py"
    spec = importlib.util.spec_from_file_location(f"bench_kind_{kind}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def quantile_grid(n: int) -> np.ndarray:
    """The midpoints ``(i + 0.5) / n`` of ``n`` equal-probability strata."""
    return (np.arange(n) + 0.5) / n


def lognormal_grid(dist: dict, n: int) -> np.ndarray:
    """Stratified lognormal lengths, clipped, as whole tokens."""
    z = np.array([NormalDist().inv_cdf(q) for q in quantile_grid(n)])
    vals = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def block_shuffled(grid: np.ndarray, n: int, gen: np.random.Generator):
    """``n`` values: whole blocks of ``grid``, each in its own order."""
    blocks = -(-n // len(grid))
    return np.concatenate([gen.permutation(grid) for _ in range(blocks)])[:n]


def n_requests(mix: dict) -> int:
    """Requests in the schedule: the mix's ``requests``, or enough for
    ``horizon_s`` at ``rate_per_s``, rounded up to whole blocks."""
    n = mix.get("requests") or math.ceil(mix["rate_per_s"] * mix["horizon_s"])
    return -(-n // mix["block"]) * mix["block"]


def stationary(mix: dict, slots: int, rng: np.random.Generator):
    """(prompts, outputs) of the ``slots`` requests a long-running engine
    holds under this mix: a slot's occupant is drawn by how long it
    stays (length-biased output ``o``) and is found at an age ``a``
    uniform over its life, so it arrives as a prompt of ``p + a`` tokens
    that still has ``o - a`` to serve.  The lengths are stratified and
    paired the same way for every seed; only their order follows it."""
    if slots == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    grid = np.sort(lognormal_grid(mix["output"], mix["block"]))
    cdf = np.cumsum(grid) / grid.sum()
    q = quantile_grid(slots)
    o = grid[np.searchsorted(cdf, q)]
    pair = weights.rng(0, "in flight")
    p = lognormal_grid(mix["prompt"], slots)[pair.permutation(slots)]
    a = np.floor(q[pair.permutation(slots)] * o).astype(np.int64)
    order = rng.permutation(slots)
    return (p + a)[order], (o - a)[order]


def generate(mix: dict, vocab: int, seed: int, root: pathlib.Path = HERE,
             slots: int = 0):
    """The schedule of one run: a list of :class:`Planned`, by due time.
    ``slots`` is the engine's slot count, for a kind that starts in
    flight."""
    n = n_requests(mix)
    block = mix["block"]
    gen = weights.rng(seed, "traffic")
    kind = load_kind(mix["kind"], root)
    prompts = block_shuffled(lognormal_grid(mix["prompt"], block), n, gen)
    outputs = block_shuffled(lognormal_grid(mix["output"], block), n, gen)
    due = kind.arrivals(mix, n, gen)
    if hasattr(kind, "in_flight"):
        p0, o0 = kind.in_flight(mix, slots, gen)
        prompts = np.concatenate([p0, prompts])
        outputs = np.concatenate([o0, outputs])
        due = np.concatenate([np.zeros(len(p0)), due])
    tokens = gen.integers(0, vocab, size=int(prompts.sum()), dtype=np.int32)
    starts = np.concatenate([[0], np.cumsum(prompts)])
    order = np.argsort(due, kind="stable")
    return [
        Planned(
            float(due[i]),
            tokens[starts[i] : starts[i + 1]],
            int(outputs[i]),
        )
        for i in order
    ]


def context_bound(mix: dict) -> int:
    """The longest context a request of this mix can hold: its longest
    prompt plus its longest output (a preempted request re-prefills as
    much)."""
    return mix["prompt"]["max"] + mix["output"]["max"]


def mean_resident(mix: dict) -> float:
    """Time-averaged tokens a slot holds under this mix: a request with
    prompt p and output o spends o decode steps holding p + i tokens at
    step i, so the average is E[o·p + o(o+1)/2] / E[o] with p and o
    independent."""
    p = lognormal_grid(mix["prompt"], mix["block"]).astype(float)
    o = lognormal_grid(mix["output"], mix["block"]).astype(float)
    return p.mean() + (np.mean(o * (o + 1) / 2)) / o.mean()
