"""Share of its roofline that the decode program of the latent-attention +
expert family reaches: the least time of the traced decode calls (per
call, the larger of its operations over the peak rate and its least bytes
over the peak bandwidth, from ``bench/work_mla_moe.py`` and the
benchmark's own per-slot contexts) over the device time of the
``jit_decode_fn`` program in the trace."""

from bench import window, work_mla_moe


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    mod = run.trace["modules"].get("jit_decode_fn")
    if not mod or not mod["seconds"]:
        return None
    cfg, pk = run.config, run.peaks
    least = sum(
        max(work_mla_moe.decode_flops(cfg, dec) / pk["bf16_flops_per_s"],
            work_mla_moe.decode_bytes(cfg, dec) / pk["hbm_bytes_per_s"])
        for dec, _ in window.traced_steps(run.rec) if dec
    )
    return 100.0 * least / mod["seconds"]
