"""collective_ms.train: device milliseconds per training step in
collective operations (all-to-all, all-gather, all-reduce,
reduce-scatter, collective-permute), mean over chips."""

from bench import trace_reduce


def read(ctx):
    f = ctx.facts
    if not f.get("steps") or f.get("chips", 1) < 2:
        return None
    s = ctx.trace.op_seconds(trace_reduce.is_collective)
    return s / f["steps"] * 1e3 if s > 0 else None
