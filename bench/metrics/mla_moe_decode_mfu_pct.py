"""The whole decode step's share of the chip's peak, latent-attention +
expert family: operations of the window's decode calls
(``bench/work_mla_moe.py``) over their wall time (host clock, blocking on
the tokens) at the peak rate."""

from bench import window, work_mla_moe


def read(run):
    if run.peaks is None:
        return None
    seconds = window.delta(run.rec, "decode_seconds")
    if not seconds:
        return None
    flops = sum(work_mla_moe.decode_flops(run.config, dec)
                for dec, _ in run.rec.steps if dec)
    return 100.0 * flops / (seconds * run.peaks["bf16_flops_per_s"])
