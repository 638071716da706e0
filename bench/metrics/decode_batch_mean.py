"""Slots per decode call in the window: decode tokens over decode calls
(the engine's ``PhaseTelemetry``, differenced across the window)."""

from bench import window


def read(run):
    calls = window.delta(run.rec, "decode_calls")
    return window.delta(run.rec, "decode_tokens") / calls if calls else None
