"""Window arithmetic: what the end-to-end and per-layer metrics are
computed from, kept apart from the loop that records it so it can be
checked on hand-made records.

Times are ``time.perf_counter()`` seconds.  A token is delivered when the
``step()`` that produced it returns.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Record:
    """What one run's loop saw, lead-in included."""

    start: float = 0.0  # window start
    end: float = 0.0  # window end: the return of its last step()
    due: dict = dataclasses.field(default_factory=dict)  # rid -> due time
    prompt_len: dict = dataclasses.field(default_factory=dict)  # rid -> p
    submitted: dict = dataclasses.field(default_factory=dict)  # rid -> t
    #: rid -> [(delivery time, token index, phase)], in delivery order
    tokens: dict = dataclasses.field(default_factory=dict)
    finished: dict = dataclasses.field(default_factory=dict)  # rid -> t
    admitted: dict = dataclasses.field(default_factory=dict)  # rid -> t
    refused: int = 0  # requests due in the window that submit() refused
    #: per step in the window: (decode contexts, prefill contexts)
    steps: list = dataclasses.field(default_factory=list)
    kv_used: list = dataclasses.field(default_factory=list)  # fractions
    #: name -> (value at window start, value at window end)
    counters: dict = dataclasses.field(default_factory=dict)
    #: index into ``steps`` of the first step the profiler traced
    trace_from: int = 0

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def inside(self, t: float) -> bool:
        return self.start <= t <= self.end


def due_in_window(rec: Record) -> list:
    return sorted(
        rid for rid, t in rec.due.items() if rec.start <= t < rec.end
    )


def delivered_tokens(rec: Record) -> int:
    return sum(
        1 for toks in rec.tokens.values() for t, _, _ in toks if rec.inside(t)
    )


def ttft_s(rec: Record) -> list:
    """For every request due in the window: from its due time to the
    delivery of its first token, or to the window's end where none came."""
    out = []
    for rid in due_in_window(rec):
        toks = rec.tokens.get(rid)
        first = toks[0][0] if toks else None
        if first is None or first > rec.end:
            first = rec.end
        out.append(first - rec.due[rid])
    return out


def queue_wait_s(rec: Record) -> list:
    """For every request due in the window: from its due time to its
    admission into a slot, or to the window's end where it waited on."""
    out = []
    for rid in due_in_window(rec):
        t = rec.admitted.get(rid)
        if t is None or t > rec.end:
            t = rec.end
        out.append(t - rec.due[rid])
    return out


def late_s(rec: Record) -> list:
    """How late the generator submitted each request due in the window:
    it submits between steps, so a long step delays the next arrivals."""
    return [rec.submitted[rid] - rec.due[rid] for rid in due_in_window(rec)]


def gaps_s(rec: Record) -> list:
    """Every gap between consecutive tokens of a request, both delivered
    inside the window (two tokens of one step make a gap of 0)."""
    out = []
    for toks in rec.tokens.values():
        times = [t for t, _, _ in toks if rec.inside(t)]
        out.extend(np.diff(times).tolist())
    return out


def traced_steps(rec: Record) -> list:
    """The steps of the window's traced part (its last seconds)."""
    return rec.steps[rec.trace_from :]


def delta(rec: Record, name: str) -> float:
    before, after = rec.counters[name]
    return after - before


def p95(values) -> float | None:
    return float(np.percentile(values, 95)) if len(values) else None
