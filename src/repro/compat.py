"""Thin helpers over the jax sharding API, plus the process-level
compile-cache switch the entry points share.

The repo is written against one jax line (``pyproject.toml``): these
helpers only fix the repo's conventions -- every mesh axis is Auto,
``shard_map`` takes the manual axes as a set -- so call sites stay short.
"""

from __future__ import annotations

import os
import pathlib

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
