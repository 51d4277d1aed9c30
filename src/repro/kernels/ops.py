"""The aggregation engine: one entry point for every MM-aggregation.

``AggregationEngine`` wraps the fused Pallas kernel (or the jnp oracle,
for contexts that cannot host a pallas_call) behind a uniform API:

  aggregate(x, a=None)          -- (K, ...) array -> (...)
  aggregate_batched(x, A)       -- (K, M) x (K, N) weight columns -> (N, M)
  aggregate_tree(tree, a=None)  -- whole gradient pytree, ONE kernel launch

Block sizes: unless the caller pins ``block_m``/``block_k``, every
launch consults ``kernels.tuning`` -- the cached autotuner winner for
the (K, M, N, dtype) workload when one exists, else a VMEM-budget
heuristic.  The lookup is shape-only, so it is safe at trace time;
running ``tuning.autotune`` (e.g. from a warmup script or the agg
benchmark) makes every subsequent engine launch for that shape use the
measured winner.

Tree path (copy-free): all leaves are staged into a single (K, M_total)
f32 buffer by one preallocated scatter (``jnp.zeros`` +
``dynamic_update_slice`` per leaf), the kernel runs once, and the
result is sliced back -- ALL inside one jitted program per tree layout,
so XLA fuses stage -> kernel -> split with no eager concatenate and no
per-leaf host dispatch (the previous path materialized an eager
``jnp.concatenate`` and then sliced eagerly per leaf: three extra
full-tree copies).  Donation semantics: with ``donate_leaves=True`` the
engine donates the input leaf buffers to the staging computation, so
XLA may write the staging buffer into the gradients' memory
(aggregation is typically the last reader of a gradient tree).  The
caller must not reuse the passed leaves afterwards -- jax will raise on
a donated-buffer re-read.  Donation is a no-op (and safe) when the call
is inlined into an outer jit.

The layout (treedef, per-leaf offsets/shapes) is computed once per tree
structure and cached on the engine, so repeated training-step calls
reuse the compiled stage->kernel->split program.

Module-level ``mm_aggregate`` / ``mm_aggregate_batched`` /
``mm_aggregate_tree`` delegate to a shared default engine and are what
the aggregator registry, diffusion, federated, sharded collectives and
the train steps call.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import location, mestimators
from repro.kernels import mm_aggregate as _k
from repro.kernels import tuning


# ---------------------------------------------------------------------------
# workload recording (launch-audit ground truth)
# ---------------------------------------------------------------------------
# Every engine launch resolves its (K, M, N, dtype) workload and block
# sizes through ``_opts``; inside a ``record_workloads()`` scope each
# distinct resolution is appended to the yielded list.  Resolution
# happens at Python/trace time (the block choice is a static jit arg),
# so wrapping ``jax.jit(...).lower()`` of a program that aggregates is
# enough to observe every workload the compiled program will launch --
# this is how the scenario runner builds a launch audit that reflects
# the geometry the engine *actually* selected (tuning-cache winner or
# heuristic), not a parallel reconstruction.

_ACTIVE_RECORDERS: list = []


@contextlib.contextmanager
def record_workloads():
    """Collect {k, m, n, dtype, backend, block_m, block_k, path, view,
    rows, cols} dicts for every distinct engine workload resolved
    inside the scope: ``view`` is the single-pass kernel's (R, C) view
    of one agent's coordinates (``mm_aggregate.leaf_view``), "rows" for
    the leaf's own matrix, "flat" for the flattened fallback (None on
    the jnp backend, which runs no kernel)."""
    records: list = []
    _ACTIVE_RECORDERS.append(records)
    try:
        yield records
    finally:
        # remove by identity, not equality: nested scopes hold
        # equal-content lists and list.remove would pop the wrong one
        for i, r in enumerate(_ACTIVE_RECORDERS):
            if r is records:
                del _ACTIVE_RECORDERS[i]
                break


def _record_workload(entry: dict) -> None:
    for records in _ACTIVE_RECORDERS:
        if entry not in records:
            records.append(dict(entry))


def _tukey(c: float):
    return (mestimators.TUKEY if c == mestimators.TUKEY_C95
            else mestimators.make_tukey(c))


_AGG_STATICS = ("num_iters", "c", "block_m", "block_k", "interpret",
                "backend", "path")


def _agg_nd_impl(x, a, *, num_iters, c, block_m, block_k, interpret, backend,
                 path=None):
    """(K, ...) -> (...), optional (K,) weights.

    The jnp backend never flattens trailing dims (the estimate is
    elementwise), so auto-axis sharding of multi-dim gradient leaves
    survives under GSPMD; the pallas kernel reads the leaf through its
    (K, R, C) view, which is the leaf's own layout where it can be.
    """
    if backend == "jnp":
        af = None if a is None else a.astype(jnp.float32)
        out = location.mm_estimate(
            x.astype(jnp.float32), a=af, loss=_tukey(c),
            num_iters=num_iters).estimate
        return out.astype(x.dtype)
    return _k.mm_aggregate_nd(x, a, num_iters=num_iters, c=c,
                              block_m=block_m, block_k=block_k,
                              interpret=interpret, path=path)


_agg_nd = jax.jit(_agg_nd_impl, static_argnames=_AGG_STATICS)
# donating variant: the caller hands the stacked cohort buffer over to
# the launch (a streaming service's assembled cohort is dead after the
# aggregate), letting XLA write intermediates into its memory
_agg_nd_donated = jax.jit(_agg_nd_impl, static_argnames=_AGG_STATICS,
                          donate_argnums=(0,))


@functools.partial(jax.jit, static_argnames=_AGG_STATICS)
def _agg_batched(x, a, *, num_iters, c, block_m, block_k, interpret,
                 backend, path=None):
    """(K, ...) x (K, N) -> (N, ...)."""
    if backend == "jnp":
        xf = x.astype(jnp.float32)
        out = jax.vmap(
            lambda col: location.mm_estimate(
                xf, a=col.astype(jnp.float32), loss=_tukey(c),
                num_iters=num_iters).estimate,
            in_axes=1)(a)
        return out.astype(x.dtype)
    return _k.mm_aggregate_nd(x, a, num_iters=num_iters, c=c,
                              block_m=block_m, block_k=block_k,
                              interpret=interpret, path=path)


def _agg_tree_impl(leaves, a, *, sizes, offsets, shapes, dtypes, opts):
    """Stage -> single kernel launch -> split, one fused program.

    ``leaves`` is the flat tuple of (K, ...) arrays; the static layout
    tuples come from the engine's _TreeLayout cache.  The staging buffer
    is preallocated once and each leaf is scattered into its column
    range; under jit the updates lower to in-place writes (and with
    donation the buffer can reuse the leaves' memory).
    """
    k = shapes[0][0]
    m_total = sum(sizes)
    buf = jnp.zeros((k, m_total), jnp.float32)
    for leaf, off, n in zip(leaves, offsets, sizes):
        buf = jax.lax.dynamic_update_slice(
            buf, leaf.astype(jnp.float32).reshape(k, n), (0, off))
    agg = _agg_nd(buf, a, **dict(opts))
    return tuple(
        jax.lax.dynamic_slice(agg, (off,), (n,)).reshape(shape[1:]).astype(dt)
        for off, n, shape, dt in zip(offsets, sizes, shapes, dtypes))


_STATIC_TREE_ARGS = ("sizes", "offsets", "shapes", "dtypes", "opts")
_agg_tree_flat = jax.jit(_agg_tree_impl, static_argnames=_STATIC_TREE_ARGS)
# donating variant: the leaf buffers may be reused for the staging
# scatter (callers must treat the passed tree as consumed)
_agg_tree_flat_donated = jax.jit(_agg_tree_impl,
                                 static_argnames=_STATIC_TREE_ARGS,
                                 donate_argnums=(0,))


class _TreeLayout:
    """Cached flatten plan for one pytree structure."""

    __slots__ = ("treedef", "shapes", "dtypes", "sizes", "offsets", "k")

    def __init__(self, treedef, leaves):
        self.treedef = treedef
        self.k = leaves[0].shape[0]
        self.shapes = tuple(l.shape for l in leaves)
        self.dtypes = tuple(l.dtype for l in leaves)
        self.sizes = tuple(int(l.size) // self.k for l in leaves)
        offs, off = [], 0
        for n in self.sizes:
            offs.append(off)
            off += n
        self.offsets = tuple(offs)

    def key(self):
        return (self.treedef, self.shapes, self.dtypes)


class AggregationEngine:
    """Weighted, batched MM-aggregation around the fused Pallas kernel.

    ``backend="pallas"`` runs the fused kernel (interpret mode on CPU);
    ``backend="jnp"`` runs the identical algorithm via core.location for
    contexts that cannot host a pallas_call (it is the kernel's oracle,
    so both backends agree to float tolerance).

    ``block_m``/``block_k`` of None (the default) resolve per launch
    through ``kernels.tuning`` (autotuned winner if cached, heuristic
    otherwise); ``autotune=True`` additionally runs the timing sweep on
    first sight of a workload shape (only outside jit tracing -- traced
    calls fall back to the cache/heuristic).  ``path`` pins the kernel
    variant (``"single"`` | ``"two_pass"``); the default ``None``
    auto-selects per workload (tuning-cache crossover winner, else the
    VMEM-model heuristic -- large-K meshes transparently take the
    two-pass K-major kernel).  ``donate_leaves=True`` lets the tree
    path donate the input gradient leaves to the staging scatter (see
    module docstring).
    """

    def __init__(self, *, num_iters: int = 10,
                 c: float = mestimators.TUKEY_C95,
                 block_m: Optional[int] = None,
                 block_k: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 backend: str = "pallas",
                 autotune: bool = False,
                 donate_leaves: bool = False,
                 path: Optional[str] = None):
        if backend not in ("pallas", "jnp"):
            raise ValueError(f"unknown backend {backend!r}")
        if path is not None and path not in _k.PATHS:
            raise ValueError(
                f"unknown kernel path {path!r}; known: {_k.PATHS}")
        self.num_iters = num_iters
        self.c = c
        self.block_m = block_m
        self.block_k = block_k
        self.interpret = interpret
        self.backend = backend
        self.autotune = autotune
        self.donate_leaves = donate_leaves
        self.path = path
        self._layouts: dict = {}

    def _blocks_for(self, x, k: int, m: int, n: int = 1):
        """Resolve block sizes for one launch: explicit engine settings
        win; otherwise consult the tuning cache (optionally running the
        sweep when ``autotune`` and ``x`` is concrete)."""
        if self.block_m is not None or self.backend != "pallas":
            bm = self.block_m if self.block_m is not None \
                else _k.DEFAULT_BLOCK_M
            return bm, self.block_k
        dtype = x.dtype
        if self.autotune and not isinstance(x, jax.core.Tracer):
            return tuning.autotune(k, m, n, dtype,
                                   num_iters=self.num_iters,
                                   interpret=self.interpret)
        if self.block_k is not None:
            return tuning.get_blocks(k, m, n, dtype)[0], self.block_k
        return tuning.get_blocks(k, m, n, dtype)

    def _opts(self, x, k: int, m: int, n: int = 1, shape=None):
        """Resolve (and record) one launch's static options; ``shape``
        is the per-agent shape the kernel sees (default x.shape[1:])."""
        entry = {"k": int(k), "m": int(m), "n": int(n),
                 "dtype": jnp.dtype(x.dtype).name, "backend": self.backend}
        if self.backend != "pallas":
            bm, bk = self._blocks_for(x, k, m, n)
            entry.update(block_m=bm, block_k=bk, path=None, view=None,
                         rows=None, cols=None)
            _record_workload(entry)
            return dict(num_iters=self.num_iters, c=self.c, block_m=bm,
                        block_k=bk, interpret=self.interpret,
                        backend=self.backend, path=None)
        if self.autotune and self.block_m is None \
                and not isinstance(x, jax.core.Tracer):
            # warm the tuning cache so the plan below picks the winner
            tuning.autotune(k, m, n, x.dtype, num_iters=self.num_iters,
                            interpret=self.interpret)
        # the plan resolves everything the launch needs -- tile sizes
        # AND the single<->two-pass path (tuning winner or the VMEM
        # crossover heuristic); recording the *resolved* geometry makes
        # the launch audits ground truth for both paths.
        plan = _k.launch_plan(
            k, m, n, dtype=x.dtype, block_m=self.block_m,
            block_k=self.block_k, path=self.path,
            shape=tuple(x.shape[1:]) if shape is None else shape)
        entry.update(block_m=plan.block_m, block_k=plan.block_k,
                     path=plan.path, view=plan.view, rows=plan.rows,
                     cols=plan.cols)
        _record_workload(entry)
        return dict(num_iters=self.num_iters, c=self.c,
                    block_m=plan.block_m, block_k=plan.block_k,
                    interpret=self.interpret, backend=self.backend,
                    path=plan.path)

    # -- arrays ------------------------------------------------------------

    def aggregate(self, x: jnp.ndarray,
                  a: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """MM location estimate along axis 0: (K, ...) -> (...)."""
        k = x.shape[0]
        m = int(x.size) // max(k, 1)
        return _agg_nd(x, a, **self._opts(x, k, m))

    def aggregate_batched(self, x: jnp.ndarray, a: jnp.ndarray) -> jnp.ndarray:
        """(K, ...) values x (K, N) weight columns -> (N, ...): every
        neighborhood of a combination matrix in one kernel launch, the
        input tile streamed from HBM exactly once regardless of N."""
        k = x.shape[0]
        m = int(x.size) // max(k, 1)
        return _agg_batched(x, a, **self._opts(x, k, m, a.shape[1]))

    # -- pytrees -----------------------------------------------------------

    def _layout_for(self, leaves, treedef) -> _TreeLayout:
        layout = _TreeLayout(treedef, leaves)
        return self._layouts.setdefault(layout.key(), layout)

    def _tree_call(self, tree, a):
        """Resolve the exact jitted program + arguments the tree path
        runs for ``tree``: (jitted fn, positional args, static kwargs,
        layout).  Shared by ``aggregate_tree`` (which executes it) and
        ``lower_tree`` (which AOT-lowers it for the jaxpr auditor)."""
        leaves, treedef = jax.tree.flatten(tree)
        layout = self._layout_for(leaves, treedef)
        m_total = sum(layout.sizes)
        opts = tuple(sorted(
            self._opts(leaves[0], layout.k, m_total,
                       shape=(m_total,)).items()))
        fn = _agg_tree_flat_donated if self.donate_leaves else _agg_tree_flat
        kwargs = dict(sizes=layout.sizes, offsets=layout.offsets,
                      shapes=layout.shapes, dtypes=layout.dtypes, opts=opts)
        return fn, (tuple(leaves), a), kwargs, layout

    def aggregate_tree(self, tree, a: Optional[jnp.ndarray] = None):
        """Aggregate a pytree of stacked (K, ...) leaves in ONE launch.

        All leaves are scattered into the cached (K, M_total) staging
        layout, aggregated by a single kernel launch, and sliced back --
        one fused jit program per tree structure (see module docstring
        for the copy-free staging and donation semantics).
        """
        if not jax.tree.leaves(tree):
            return tree
        fn, args, kwargs, layout = self._tree_call(tree, a)
        outs = fn(*args, **kwargs)
        return jax.tree.unflatten(layout.treedef, list(outs))

    def lower_tree(self, tree, a: Optional[jnp.ndarray] = None):
        """AOT-lower (do not execute) the exact stage->kernel->split
        program ``aggregate_tree`` would run -- same jit callable, same
        static layout args, same donation setting.  Returns the jax
        ``Lowered``; ``repro.analysis.jaxpr_audit`` uses it to verify
        one-pallas_call-per-layout and that ``donate_leaves`` is
        actually reflected in the lowered program's donated buffers
        (``Lowered.args_info``)."""
        fn, args, kwargs, _ = self._tree_call(tree, a)
        return fn.lower(*args, **kwargs)

    # -- standalone launches (cohort assembly decoupled) -------------------

    def lower_launch(self, k: int, m: int, dtype=jnp.float32, *,
                     weighted: bool = True, donate: bool = False):
        """AOT-lower the one-cohort launch program for a fixed geometry:
        ``(x (k, m) dtype, a (k,) f32) -> (m,) dtype``.

        This is the "kernel launch" half of an aggregation with the
        "cohort assembly" half cut away: the caller owns staging the
        per-agent updates into the ``(k, m)`` buffer (``repro.serve``
        does it from a streaming admission buffer), compiles this
        program ONCE per cohort geometry, and launches the compiled
        executable for every admitted cohort -- no per-cohort retrace.
        The workload resolution (tuning-cache winner or heuristic, the
        single<->two-pass path) is identical to ``aggregate``'s and is
        recorded for launch audits.  ``donate=True`` donates the cohort
        buffer to the launch (it is dead after the aggregate); the
        caller must re-stage on retry rather than re-use it.
        """
        x = jax.ShapeDtypeStruct((k, m), jnp.dtype(dtype))
        opts = self._opts(x, k, m)
        a = jax.ShapeDtypeStruct((k,), jnp.float32) if weighted else None
        fn = _agg_nd_donated if donate else _agg_nd
        return fn.lower(x, a, **opts)


@functools.lru_cache(maxsize=None)
def get_engine(**kwargs) -> AggregationEngine:
    """Shared engines, memoized by configuration."""
    return AggregationEngine(**kwargs)


def _engine(num_iters, c, block_m, block_k, interpret, backend, path=None):
    return get_engine(num_iters=num_iters, c=c, block_m=block_m,
                      block_k=block_k, interpret=interpret, backend=backend,
                      path=path)


def mm_aggregate(
    x: jnp.ndarray,
    a: Optional[jnp.ndarray] = None,
    *,
    num_iters: int = 10,
    c: float = mestimators.TUKEY_C95,
    block_m: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    backend: str = "pallas",
    path: Optional[str] = None,
) -> jnp.ndarray:
    """MM location estimate along axis 0: (K, ...) -> (...)."""
    return _engine(num_iters, c, block_m, block_k, interpret,
                   backend, path).aggregate(x, a)


def mm_aggregate_batched(
    x: jnp.ndarray,
    a: jnp.ndarray,
    *,
    num_iters: int = 10,
    c: float = mestimators.TUKEY_C95,
    block_m: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    backend: str = "pallas",
    path: Optional[str] = None,
) -> jnp.ndarray:
    """Batched weighted aggregation: (K, ...) x (K, N) -> (N, ...)."""
    return _engine(num_iters, c, block_m, block_k, interpret,
                   backend, path).aggregate_batched(x, a)


def mm_aggregate_tree(
    tree,
    a: Optional[jnp.ndarray] = None,
    *,
    num_iters: int = 10,
    c: float = mestimators.TUKEY_C95,
    block_m: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    backend: str = "pallas",
    path: Optional[str] = None,
):
    """Aggregate a pytree of stacked (K, ...) leaves in ONE kernel launch."""
    return _engine(num_iters, c, block_m, block_k, interpret,
                   backend, path).aggregate_tree(tree, a)
