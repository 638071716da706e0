"""95th percentile, over every request due in the window, of the time
from its due time to its admission into a slot (``admitted_at``),
censored at the window's end."""

from bench import window


def read(run):
    p = window.p95(window.queue_wait_s(run.rec))
    return None if p is None else 1e3 * p
