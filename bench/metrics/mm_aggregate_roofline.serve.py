"""mm_aggregate_roofline.serve: the MM-aggregation kernel's share of its
HBM roofline per service launch.  Bytes one launch needs (one read of
the (k_min, M) cohort, its weights, one write of the (M,) estimate;
bench/work.py) over the chip's HBM bandwidth, over the kernel's device
time per launch in the window, in %.  Only the HBM bound is counted."""

from bench import trace_reduce, work


def read(ctx):
    f = ctx.facts
    if not f.get("commits"):
        return None
    s = ctx.trace.op_seconds(trace_reduce.is_mm_kernel) / f["commits"]
    if s <= 0:
        return None
    bw = work.peaks(f["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * f["launch_bytes"] / bw / s
