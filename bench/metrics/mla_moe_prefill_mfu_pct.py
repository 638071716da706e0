"""Operations of the traced prefills of the latent-attention + expert
family (unpadded tokens, causal attention counted once per pair, from
``bench/work_mla_moe.py``) over the device time of the ``jit_prefill_fn``
program in the trace at the chip's peak rate."""

from bench import window, work_mla_moe


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    mod = run.trace["modules"].get("jit_prefill_fn")
    if not mod or not mod["seconds"]:
        return None
    flops = sum(
        work_mla_moe.prefill_flops(run.config, n)
        for _, pre in window.traced_steps(run.rec) for n in pre
    )
    return 100.0 * flops / (mod["seconds"] * run.peaks["bf16_flops_per_s"])
