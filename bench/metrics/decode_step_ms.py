"""Wall time of one decode call, blocking on its tokens (the engine's
``PhaseTelemetry``, differenced across the window)."""

from bench import window


def read(run):
    calls = window.delta(run.rec, "decode_calls")
    return 1e3 * window.delta(run.rec, "decode_seconds") / calls if calls else None
