"""host_ms_per_commit.serve: host milliseconds per commit spent in the
service's own host path: the harness's timed offer and pump calls in
the window, less the service's launch wall time, over the window's
commits."""


def read(ctx):
    f = ctx.facts
    if not f.get("commits"):
        return None
    return 1e3 * (f["host_s"] - sum(f["launch_wall_s"])) / f["commits"]
