"""setup_lower_s.train: seconds the run spent tracing the training step
to a jaxpr and lowering it to an MLIR module (the step's ``lower()``,
which the train driver makes once, in set-up).  Read in the run's own
process from the program's compile totals per function
(``repro.compat.compile_totals``: jax.monitoring's trace and lower
durations); None from a program that keeps no such totals, or where
the step never compiled."""

# the program's train step (repro.launch.steps.make_train_step_gspmd)
STEP = "step"


def read(ctx):
    from repro import compat
    totals = getattr(compat, "compile_totals", None)
    t = totals(STEP) if totals else None
    if not t or not t["compiles"]:
        return None
    return t["trace_s"] + t["lower_s"]
