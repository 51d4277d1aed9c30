"""Thin helpers over the jax sharding API, plus the process-level
compile glue the entry points share: the compile-cache switch, the
compile clock and the process's compile totals per function.

The repo is written against one jax line (``pyproject.toml``): these
helpers only fix the repo's conventions -- every mesh axis is Auto,
``shard_map`` takes the manual axes as a set -- so call sites stay short.
"""

from __future__ import annotations

import contextlib
import os
import pathlib
import re
import threading
import time

import jax

COMPILATION_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed in-checkout default (listed in .gitignore, and what ci.sh uses):
# the cache key includes the path, so a moving directory never hits
DEFAULT_COMPILATION_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[2] / ".jax_compile_cache")


def enable_persistent_compilation_cache() -> str:
    """Point jax's persistent (on-disk) compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when it is set, else at the fixed
    ``<repo>/.jax_compile_cache``, and drop the min-compile-time /
    min-entry-size thresholds so even small programs persist.  Called
    once by each entry point (scripts, benchmarks, the trainer), never
    at import.  Returns the directory used."""
    path = os.environ.get(COMPILATION_CACHE_ENV) or DEFAULT_COMPILATION_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# jax.monitoring duration events of one jit's way to an executable, by the
# compile-clock key they feed.  Backend compile wraps the persistent
# cache's lookup, so a cache load counts as a compile too.
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_ACTIVE_CLOCKS: list = []
# per function name: the process's compile seconds and backend compiles
# (jit compiles may run on several threads at once)
_TOTALS: dict = {}
_TOTALS_LOCK = threading.Lock()
_ZERO_TOTALS = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
                "compiles": 0}
# a lowering or compile event names its function "jit(step)", a trace "step"
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def _on_compile_duration(event, duration, fun_name=None, **_):
    key = _COMPILE_EVENTS.get(event)
    if key is None:
        return
    end = time.perf_counter()
    for events in _ACTIVE_CLOCKS:
        events.append((key, end - duration, end))
    if fun_name is not None:
        m = _WRAPPED.match(fun_name)
        with _TOTALS_LOCK:
            tot = _TOTALS.setdefault(m.group(1) if m else fun_name,
                                     dict(_ZERO_TOTALS))
            tot[key] += duration
            tot["compiles"] += key == "compile_s"


def _on_compile_event(event, **_):
    if event == _CACHE_HIT_EVENT:
        for events in _ACTIVE_CLOCKS:
            events.append(("cache_hits", 0.0, 0.0))


# registered at import, so that the totals hold every compile after the
# first import of the package (jax.monitoring's listeners are process-wide
# and cannot be removed); they cost microseconds per event
jax.monitoring.register_event_duration_secs_listener(_on_compile_duration)
jax.monitoring.register_event_listener(_on_compile_event)


def _union_s(spans) -> float:
    total, reach = 0.0, float("-inf")
    for s, e in sorted(spans):
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total


@contextlib.contextmanager
def compile_clock():
    """Time the compiles inside the scope.  Yields a dict that is filled
    on exit: ``trace_s`` (jaxpr tracing), ``lower_s`` (to an MLIR module),
    ``compile_s`` (backend compile or persistent-cache load), each the
    wall time covered by its events and none counted twice (a jit traced
    while an outer one traces or lowers belongs to the outer), plus
    ``compiles`` (backend-compile events) and ``cache_hits``.  Scopes
    nest; each sees every compile inside it."""
    events: list = []
    out: dict = {}
    _ACTIVE_CLOCKS.append(events)
    try:
        yield out
    finally:
        # remove by identity: nested scopes hold equal-content lists
        for i, e in enumerate(_ACTIVE_CLOCKS):
            if e is events:
                del _ACTIVE_CLOCKS[i]
                break
        seen: list = []
        for key in ("trace_s", "lower_s", "compile_s"):
            spans = [(s, e) for k, s, e in events if k == key]
            out[key] = _union_s(seen + spans) - _union_s(seen)
            seen += spans
        out["compiles"] = sum(k == "compile_s" for k, _, _ in events)
        out["cache_hits"] = sum(k == "cache_hits" for k, _, _ in events)


def compile_totals(fun_name: str) -> dict:
    """What this process has spent so far on the jits of functions named
    ``fun_name`` (the Python function's ``__name__``): ``trace_s``,
    ``lower_s`` and ``compile_s`` (backend compile or persistent-cache
    load), summed over its compiles, and ``compiles``.  Counted from the
    first import of this module; a function never compiled reads zeros.
    Tracing time is the function's own jaxpr trace, which holds the
    traces of the jits it calls."""
    with _TOTALS_LOCK:
        return dict(_TOTALS.get(fun_name, _ZERO_TOTALS))


def make_mesh(axis_shapes, axis_names):
    """A mesh whose axes are all Auto (GSPMD decides the layouts)."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def get_abstract_mesh():
    """The mesh of the current trace context, or None when no mesh (or
    an empty one) is active."""
    am = jax.sharding.get_abstract_mesh()
    return am if am is not None and am.shape else None


def shard_map(f, mesh=None, *, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map`` with ``axis_names`` (the manual axes) taken as
    any iterable; ``mesh=None`` resolves the context mesh."""
    kwargs = {}
    if mesh is not None:
        kwargs["mesh"] = mesh
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma, **kwargs)
