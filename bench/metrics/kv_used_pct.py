"""Mean share of the page pool in use, sampled after each step of the
window."""


def read(run):
    used = run.rec.kv_used
    return 100.0 * sum(used) / len(used) if used else None
