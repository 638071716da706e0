"""Offline batch: the whole schedule is due at the start of the lead-in,
so the queue never empties while the schedule lasts.  The run starts in
the backlog's stationary state: every slot already holds a request part
way through its output (``traffic.stationary``), so the window measures
the mixed steady state and not the first cohort of requests."""

import numpy as np

from bench.traffic import stationary


def arrivals(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    return np.zeros(n)


def in_flight(mix: dict, slots: int, rng: np.random.Generator):
    return stationary(mix, slots, rng)
