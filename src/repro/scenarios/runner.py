"""Lower a ``ScenarioSpec`` to one ``lax.scan`` loop and run it.

The runner owns the *only* scenario loop in the repo: every paradigm
contributes a thin adapter (``registry.register_paradigm``) that maps a
spec to ``(state0, step_fn)`` (or a full ``registry.Lowering``), and
``run(spec)`` AOT-compiles the scan over ``spec.num_steps`` PRNG keys
(``compile_s``), executes it (``wall_clock_s``, never including
compilation), collects the uniform per-step metrics (msd / loss /
consensus), summarizes attack success against a spec-derived breakdown
level, and attaches a launch audit built from the pallas workloads the
aggregation engine actually resolved during tracing
(``kernels.ops.record_workloads``).

``diffusion_loop`` / ``federated_loop`` are the same step functions
scanned without the spec layer; ``core.diffusion.run_diffusion`` and
``core.federated.run_federated`` delegate here so the legacy public
API and the scenario subsystem share one loop body (bit-for-bit).

The sharded paradigm defaults to the stacked single-program lowering
(mathematically identical to the shard_map collectives -- rs_mm is an
exact reshard of the same estimator); ``paradigm_kwargs``
``(("collective", "rs_mm"),)`` opts into the real per-rank
``core.sharded.robust_all_reduce`` lowering on a K-device mesh (the
building block the robust-FSDP train step uses per layer).
"""

from __future__ import annotations

import collections
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import diffusion, federated, sharded
from repro.data import synthetic
from repro.scenarios import metrics, registry
from repro.scenarios.spec import ScenarioResult, ScenarioSpec


# ===========================================================================
# the one scan loop
# ===========================================================================

def scan_loop(step_fn, state0, key, num_steps: int):
    """Scan ``step_fn(state, key_i, i) -> (state, metrics_dict)`` over
    ``num_steps`` split keys; returns (final state, stacked metrics)."""
    keys = jax.random.split(key, num_steps)

    def body(state, xs):
        key_i, i = xs
        return step_fn(state, key_i, i)

    return jax.lax.scan(body, state0, (keys, jnp.arange(num_steps)))


# ===========================================================================
# paradigm step functions (shared by spec adapters and legacy wrappers)
# ===========================================================================

def _diffusion_step_fn(grad_fn, comb, config, w_star):
    def step(w, key, i):
        w_next = diffusion.diffusion_step(
            w, key, grad_fn=grad_fn, combination=comb, config=config, step=i)
        # benign set at THIS step: time-varying schedules move the
        # malicious identity, and metrics must average over the agents
        # that were honest when the step ran (static schedules ignore i,
        # preserving the historical mask bit-for-bit).
        benign = ~config.byzantine.malicious_mask(w.shape[0], i)
        return w_next, {
            "msd": diffusion.msd(w_next, w_star, benign),
            "consensus": metrics.consensus_distance(w_next, benign),
        }
    return step


def _federated_step_fn(grad_fn, config, w_star):
    def step(w, key, i):
        w_next = federated.federated_round(
            w, key, grad_fn=grad_fn, config=config, step=i)
        return w_next, {
            "msd": metrics.msd_single(w_next, w_star),
            "consensus": jnp.zeros((), w_next.dtype),
        }
    return step


def _sharded_step_fn(grad_fn, agg_fn, byz, k_agents, step_size, w_star):
    """Distributed-SGD-with-robust-all-reduce, stacked lowering: one
    shared model, K per-agent gradients, one robust aggregate per step
    (the Mode-A train-step semantics on the linear problem)."""
    def step(w, key, i):
        g_key, a_key = jax.random.split(key)
        grads = grad_fn(jnp.broadcast_to(w, (k_agents,) + w.shape), g_key)
        grads = byz.apply(grads, a_key, i)
        w_next = w - step_size * agg_fn(grads, None)
        return w_next, {
            "msd": metrics.msd_single(w_next, w_star),
            "consensus": jnp.zeros((), w_next.dtype),
        }
    return step


def _sharded_collective_step_fn(grad_fn, byz, k_agents, step_size, w_star,
                                method, agg_name, agg_kwargs):
    """Real shard_map lowering: each rank owns one agent's gradient and
    the aggregate is a ``core.sharded.robust_all_reduce`` collective --
    the same building block the robust-FSDP train step applies per
    layer.  PRNG keys cross the shard_map boundary as raw key data."""
    mesh = compat.make_mesh((k_agents,), ("agents",))

    def per_rank(w, key_data, i):
        key = jax.random.wrap_key_data(key_data)
        g_key, a_key = jax.random.split(key)
        # the stacked draw is replicated so every rank sees the same
        # samples (collusion attacks need the full stack); each rank
        # then keeps only its own row for the collective.
        grads = grad_fn(jnp.broadcast_to(w, (k_agents,) + w.shape), g_key)
        grads = byz.apply(grads, a_key, i)
        g_own = grads[jax.lax.axis_index("agents")]
        est = sharded.robust_all_reduce(
            g_own, "agents", method=method, aggregator=agg_name,
            **agg_kwargs)
        return w - step_size * est

    smapped = compat.shard_map(per_rank, mesh=mesh,
                               in_specs=(P(), P(), P()), out_specs=P(),
                               check_vma=False)

    def step(w, key, i):
        w_next = smapped(w, jax.random.key_data(key), i)
        return w_next, {
            "msd": metrics.msd_single(w_next, w_star),
            "consensus": jnp.zeros((), w_next.dtype),
        }
    return step


# ===========================================================================
# legacy loops (called by core.diffusion / core.federated wrappers)
# ===========================================================================

def diffusion_loop(*, grad_fn, combination, config, w_star, num_iters: int,
                   key, w0=None):
    """The REF-Diffusion loop; returns (final W, {metric: (T,) array})."""
    combination_np = np.asarray(combination)
    diffusion.check_compatible(config, combination_np)
    k_agents = combination_np.shape[0]
    if w0 is None:
        w0 = jnp.zeros((k_agents, w_star.shape[0]), dtype=w_star.dtype)
    comb = jnp.asarray(combination, dtype=w0.dtype)
    step = _diffusion_step_fn(grad_fn, comb, config, w_star)
    return scan_loop(step, w0, key, num_iters)


def federated_loop(*, grad_fn, config, w_star, num_rounds: int, key, w0=None):
    """The FedAvg-with-robust-server loop; returns (final w, metrics)."""
    if w0 is None:
        w0 = jnp.zeros_like(w_star)
    step = _federated_step_fn(grad_fn, config, w_star)
    return scan_loop(step, w0, key, num_rounds)


# ===========================================================================
# spec adapters
# ===========================================================================

def _problem(spec: ScenarioSpec) -> synthetic.LinearModelProblem:
    return synthetic.LinearModelProblem(
        dim=spec.dim, noise_var=spec.noise_var, seed=spec.data_seed)


@registry.register_paradigm("diffusion")
def _diffusion_adapter(spec: ScenarioSpec):
    problem = _problem(spec)
    grad_fn = synthetic.make_stacked_grad_fn(
        problem, spec.num_agents, data=spec.data,
        alpha=spec.dirichlet_alpha, seed=spec.data_seed)
    agg_name, _ = spec.resolved_aggregator()
    config = diffusion.DiffusionConfig(
        step_size=spec.step_size, aggregator=agg_name,
        agg_kwargs=spec.agg_kwargs, byzantine=spec.byzantine())
    comb_np = spec.combination()
    diffusion.check_compatible(config, comb_np)
    w_star = problem.w_star
    w0 = jnp.zeros((spec.num_agents, spec.dim), dtype=w_star.dtype)
    comb = jnp.asarray(comb_np, dtype=w0.dtype)
    return w0, _diffusion_step_fn(grad_fn, comb, config, w_star)


@registry.register_paradigm("federated")
def _federated_adapter(spec: ScenarioSpec):
    problem = _problem(spec)
    grad_fn = synthetic.make_client_grad_fn(
        problem, spec.num_agents, data=spec.data,
        alpha=spec.dirichlet_alpha, seed=spec.data_seed)
    agg_name, _ = spec.resolved_aggregator()
    config = federated.FederatedConfig(
        num_clients=spec.num_agents,
        clients_per_round=spec.clients_per_round(),
        local_steps=spec.local_steps, step_size=spec.step_size,
        aggregator=agg_name, agg_kwargs=spec.agg_kwargs,
        byzantine=spec.byzantine())
    w_star = problem.w_star
    w0 = jnp.zeros_like(w_star)
    return w0, _federated_step_fn(grad_fn, config, w_star)


@registry.register_paradigm("substrate")
def _substrate_adapter(spec: ScenarioSpec):
    # lazy: the substrate pulls the whole training stack (launch/models/
    # optim/configs); linear-paradigm users must not pay that import
    from repro.scenarios import substrate
    return substrate.lower(spec)


@registry.register_paradigm("sharded")
def _sharded_adapter(spec: ScenarioSpec):
    problem = _problem(spec)
    grad_fn = synthetic.make_stacked_grad_fn(
        problem, spec.num_agents, data=spec.data,
        alpha=spec.dirichlet_alpha, seed=spec.data_seed)
    agg_name, agg_kw = spec.resolved_aggregator()
    byz = spec.byzantine()
    w_star = problem.w_star
    w0 = jnp.zeros_like(w_star)
    collective = dict(spec.paradigm_kwargs).get("collective")
    if collective:
        if spec.backend == "pallas":
            raise ValueError(
                "collective sharded scenarios run inside shard_map, which "
                "cannot host a pallas_call; use backend='jnp'")
        if jax.local_device_count() < spec.num_agents:
            raise RuntimeError(
                f"collective sharded scenario needs >= {spec.num_agents} "
                f"devices, have {jax.local_device_count()}")
        method = "mean" if agg_name == "mean" else collective
        step = _sharded_collective_step_fn(
            grad_fn, byz, spec.num_agents, spec.step_size, w_star,
            method, agg_name, agg_kw)
    else:
        agg_fn = sharded.engine_aggregator(agg_name, **agg_kw)
        step = _sharded_step_fn(grad_fn, agg_fn, byz, spec.num_agents,
                                spec.step_size, w_star)
    return w0, step


# ===========================================================================
# run
# ===========================================================================

# In-process executable cache: AOT lower+compile dominates small runs
# (BENCH_agg.json: 100-400x steady wall, e.g. 3.73 s compile vs 25 ms
# steady for the diffusion pallas spec), and every ``run`` used to
# re-trace because the scan closure is rebuilt per call.  The spec is
# frozen/hashable and fully determines the adapter's lowering and the
# scan's input avals, so (spec, tuning-state) -> compiled executable is
# sound: the tuning fingerprint guards against a new autotune winner /
# $REPRO_TUNING_CACHE changing the kernel geometry the cached program
# was compiled with.  The recorded engine workloads ride along so cache
# hits carry the same launch audit the compile produced.
_EXEC_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_EXEC_CACHE_MAX = 32


def clear_executable_cache() -> None:
    _EXEC_CACHE.clear()


def executable_cache_size() -> int:
    return len(_EXEC_CACHE)


def _exec_cache_key(spec: ScenarioSpec):
    from repro.kernels import tuning  # deferred: keep import light
    return (spec, jax.default_backend(), tuning.cache_state())

def _audit_from_records(records) -> Optional[dict]:
    """Launch audit from the workloads the engine *actually resolved*
    while the run's scan program was traced (``ops.record_workloads``):
    one ``mm_aggregate.launch_plan`` dict per distinct pallas workload
    -- same (K, M, N, dtype) and block sizes the pallas_call was
    configured with (tuning-cache winner or heuristic), so the audit is
    ground truth, not a parallel reconstruction.  A single-workload run
    (the linear paradigms) yields the plan dict directly; multi-layout
    runs (the substrate aggregates per param leaf) yield
    ``{"layouts": [...], "n_layouts": N}``."""
    pallas = [r for r in records if r["backend"] == "pallas"]
    if not pallas:
        return None
    from repro.kernels import mm_aggregate  # deferred: keep import light
    plans = []
    for r in pallas:
        d = mm_aggregate.plan_of_record(r)._asdict()
        d["grid"] = list(d["grid"])
        plans.append(d)
    if len(plans) == 1:
        return plans[0]
    return {"layouts": plans, "n_layouts": len(plans)}


def _validated_override(state0, w0, spec: ScenarioSpec):
    """Validate a ``w0`` state override against the adapter's ``state0``
    (structure and per-leaf shape; dtype is cast to the adapter's).  A
    wrong-shape override used to broadcast silently in the stacked
    paradigms or error deep inside the scan -- fail fast instead."""
    exp_leaves, exp_def = jax.tree.flatten(state0)
    got_leaves, got_def = jax.tree.flatten(
        jax.tree.map(jnp.asarray, w0))
    if got_def != exp_def:
        raise ValueError(
            f"w0 override for paradigm {spec.paradigm!r} has tree "
            f"structure {got_def}, but the adapter's initial state is "
            f"{exp_def}")
    out = []
    for i, (e, g) in enumerate(zip(exp_leaves, got_leaves)):
        if tuple(g.shape) != tuple(e.shape):
            raise ValueError(
                f"w0 override leaf {i} has shape {tuple(g.shape)}, but "
                f"paradigm {spec.paradigm!r} expects state of shape "
                f"{tuple(e.shape)} (e.g. (K, M) stacked agent models "
                f"for diffusion, (M,) for federated/sharded)")
        out.append(g.astype(e.dtype))
    return jax.tree.unflatten(exp_def, out)


def _lowered_state(spec: ScenarioSpec, w0=None):
    """Resolve a spec through its paradigm adapter: (lowering, state0,
    key) -- the pieces both ``run`` and ``trace_spec`` scan over."""
    adapter = registry.get_paradigm(spec.paradigm)
    low = registry.as_lowering(adapter(spec))
    state0 = low.state0
    if w0 is not None:
        state0 = _validated_override(state0, w0, spec)
    return low, state0, jax.random.key(spec.seed)


def trace_spec(spec: ScenarioSpec, *, w0=None):
    """Trace (do not compile or execute) the exact scan program
    ``run(spec)`` launches.

    Returns ``(closed_jaxpr, records)``: the program's jaxpr and the
    engine workloads resolved while tracing (``ops.record_workloads``).
    This is the executable handle ``repro.analysis.jaxpr_audit`` walks
    to assert structural invariants (one pallas_call per tree layout,
    no callbacks in the steady path) on the program a scenario really
    runs -- not a reconstruction of it.
    """
    from repro.kernels import ops  # deferred: keep import light
    low, state0, key = _lowered_state(spec, w0)

    def _scan(s0, k):
        return scan_loop(low.step_fn, s0, k, spec.num_steps)

    with ops.record_workloads() as records:
        jaxpr = jax.make_jaxpr(_scan)(state0, key)
    return jaxpr, list(records)


def run(spec: ScenarioSpec, *, w0=None) -> ScenarioResult:
    """Lower the spec through its paradigm adapter and run the scan.

    The scan program is AOT-lowered and compiled first (``compile_s``),
    then executed (``wall_clock_s``) -- steady wall clock never includes
    XLA compilation.  A repeated run of an *identical* spec reuses the
    in-process compiled executable (``compile_cache_hit=True``,
    ``compile_s=0``) instead of re-tracing/re-compiling; the steady
    wall clock is unaffected (same program).  Histories come back as
    numpy; ``loss`` semantics are paradigm-owned (the linear adapters
    derive the expected excess streaming MSE msd + sigma_v^2; the
    substrate reports real training loss).  ``w0`` overrides the
    adapter's initial state after shape/structure validation (the
    executable is state-agnostic, so overrides hit the cache too).
    """
    from repro.kernels import ops  # deferred: keep import light
    low, state0, key = _lowered_state(spec, w0)

    cache_key = _exec_cache_key(spec)
    cached = _EXEC_CACHE.get(cache_key)
    if cached is not None:
        _EXEC_CACHE.move_to_end(cache_key)
        compiled, records = cached
        compile_s, cache_hit = 0.0, True
    else:
        def _scan(s0, k):
            return scan_loop(low.step_fn, s0, k, spec.num_steps)

        t0 = time.perf_counter()
        with ops.record_workloads() as records:
            compiled = jax.jit(_scan).lower(state0, key).compile()
        compile_s = time.perf_counter() - t0
        cache_hit = False
        _EXEC_CACHE[cache_key] = (compiled, list(records))
        while len(_EXEC_CACHE) > _EXEC_CACHE_MAX:
            _EXEC_CACHE.popitem(last=False)

    t0 = time.perf_counter()
    final_state, hist = compiled(state0, key)
    hist = jax.block_until_ready(hist)
    wall = time.perf_counter() - t0

    history = {name: np.asarray(h) for name, h in hist.items()}
    if low.finalize is not None:
        history = low.finalize(history)
    else:
        # linear-model default: expected excess streaming MSE
        history["loss"] = history["msd"] + spec.noise_var
    level = low.breakdown_level if low.breakdown_level is not None \
        else metrics.breakdown_threshold(spec)
    return ScenarioResult(
        spec=spec,
        history=history,
        summary=metrics.attack_summary(history["msd"],
                                       breakdown_level=level),
        wall_clock_s=wall,
        compile_s=compile_s,
        compile_cache_hit=cache_hit,
        launch_audit=_audit_from_records(records),
        final_state=final_state,
    )
