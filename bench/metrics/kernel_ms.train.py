"""kernel_ms.train: device milliseconds per training step spent in the
Mosaic MM-aggregation kernel, summed from its events in the trace's
window and divided by the steps of the window (mean over chips)."""

from bench import trace_reduce


def read(ctx):
    f = ctx.facts
    if not f.get("steps") or not f.get("use_kernel"):
        return None
    s = ctx.trace.op_seconds(trace_reduce.is_mm_kernel)
    return s / f["steps"] * 1e3 if s > 0 else None
