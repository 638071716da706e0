"""95th percentile, over every request due in the window, of the time
from its due time to the return of the ``step()`` that delivered its
first token; one with no first token by the window's end counts with its
wait so far."""

from bench import window


def read(run):
    p = window.p95(window.ttft_s(run.rec))
    return None if p is None else 1e3 * p
