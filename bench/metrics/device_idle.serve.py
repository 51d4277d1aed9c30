"""device_idle.serve: share of the traced window in which the chips run
no operation (1 - busy / window, busy the union of the operations'
intervals, mean over chips), in %."""


def read(ctx):
    w = ctx.trace.window_s
    return 100.0 * (1.0 - ctx.trace.busy_s / w) if w > 0 else None
