"""setup_compile_s.train: seconds the run spent compiling the training
step or loading it from the persistent compile cache (the step's
``compile()``, which the train driver makes once, in set-up).  Read in
the run's own process from the program's compile totals per function
(``repro.compat.compile_totals``: jax.monitoring's backend compile
durations, which wrap the cache's lookup); None from a program that
keeps no such totals, or where the step never compiled."""

# the program's train step (repro.launch.steps.make_train_step_gspmd)
STEP = "step"


def read(ctx):
    from repro import compat
    totals = getattr(compat, "compile_totals", None)
    t = totals(STEP) if totals else None
    if not t or not t["compiles"]:
        return None
    return t["compile_s"]
