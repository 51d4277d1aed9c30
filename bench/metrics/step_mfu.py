"""step_mfu: the whole training step's share of the chips' bf16 peak.
Model FLOPs per step (bench/work.py, from shapes) over the traced
window's host-clock step time x chips x peak FLOP/s, in %."""

from bench import work


def read(ctx):
    f = ctx.facts
    if not f.get("steps"):
        return None
    peak = work.peaks(f["device_kind"])["bf16_flops"]
    return 100.0 * f["flops_per_step"] / (f["step_s"] * f["chips"] * peak)
