"""launch_ms.serve: mean wall milliseconds of the service's launch over
the window's commits, as the service times it (ServeTelemetry's
launch_wall_s: staging the cohort to the device, the launch,
block_until_ready and the fetch of the estimate)."""


def read(ctx):
    w = ctx.facts.get("launch_wall_s") or []
    return 1e3 * sum(w) / len(w) if w else None
