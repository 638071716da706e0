"""Wall time of the window's prefills (insert and first-token read
included) per thousand unpadded prompt tokens."""

from bench import window


def read(run):
    tokens = window.delta(run.rec, "prefill_tokens")
    if not tokens:
        return None
    return 1e6 * window.delta(run.rec, "prefill_seconds") / tokens
