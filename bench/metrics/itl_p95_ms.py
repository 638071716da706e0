"""95th percentile of every gap between consecutive tokens of a request,
as ``step()`` delivers them inside the window."""

from bench import window


def read(run):
    p = window.p95(window.gaps_s(run.rec))
    return None if p is None else 1e3 * p
