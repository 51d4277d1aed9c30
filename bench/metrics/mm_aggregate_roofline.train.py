"""mm_aggregate_roofline.train: the MM-aggregation kernel's share of its
HBM roofline in a training step.  Bytes the algorithm needs (one read of
the (K, M) gradient stack, one write of the (M,) result; bench/work.py)
over the chip's HBM bandwidth, over the kernel's device time per step
and chip, in %.  Only the HBM bound is counted: no peak for the vector
unit's operations is in the table."""

from bench import trace_reduce, work


def read(ctx):
    f = ctx.facts
    if not f.get("steps") or not f.get("use_kernel") \
            or not f.get("agg_bytes_per_step"):
        return None
    s = ctx.trace.op_seconds(trace_reduce.is_mm_kernel) / f["steps"]
    if s <= 0:
        return None
    bw = work.peaks(f["device_kind"])["hbm_bytes_per_s"]
    per_chip = f["agg_bytes_per_step"] / f["chips"]
    return 100.0 * per_chip / bw / s
