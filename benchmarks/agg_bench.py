"""Aggregator micro-benchmark (the paper has no timing table; this is
the systems-side cost table for EXPERIMENTS.md): wall time per call for
each aggregator over (K, M), the Pallas kernel (interpret on CPU), the
batched N-neighborhood kernel, and the engine's weighted-pytree path --
including two structural audits:

  * launch audit: the whole gradient pytree is aggregated by ONE
    pallas_call, not one per leaf;
  * traffic audit: at fixed tile sizes the batched kernel fetches the
    SAME number of input blocks (and bytes) from HBM for every N --
    the one-residency contract, audited for BOTH kernel paths (the
    two-pass audit additionally pins modeled VMEM residency <= budget
    and total modeled traffic <= 2x the single-pass model).  The
    pre-batching kernel streamed the update matrix once per weight
    column (N x the bytes).

Also included: large-cohort rows timing the two-pass K-major kernel
(K >= 256, where the single-pass VMEM plan overflows) and an
IRLS-depth sweep (num_iters in {3, 5, 10} at fixed K, M) recording
us_per_call and MSD against a converged (T=50) oracle, so the default
T=10 is justified by data rather than convention.

``--json PATH`` writes the rows + audits as BENCH_agg.json so the perf
trajectory is tracked across PRs; ``--smoke`` shrinks shapes/reps for
the ci.sh invocation.  Any non-finite kernel output aborts with a
non-zero exit.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro import compat
from repro.core import aggregators
from repro.kernels import mm_aggregate as mk
from repro.kernels import ops, ref

SHAPES = ((16, 1 << 16), (32, 1 << 18))
SMOKE_SHAPES = ((8, 1 << 12),)
# two-pass territory: meshes past the single-pass VMEM sweet spot
LARGE_K_SHAPES = ((256, 1 << 14), (1024, 1 << 13))
SMOKE_LARGE_K_SHAPES = ((256, 1 << 12),)
IRLS_DEPTHS = (3, 5, 10)
AGGS = ("mean", "median", "trimmed_mean", "geometric_median", "krum",
        "m_huber", "mm_tukey")
SMOKE_AGGS = ("mean", "median", "mm_tukey")


# a small transformer-block-shaped gradient pytree, stacked over K agents
def _grad_tree(k: int, scale: int = 1):
    key = jax.random.key(0)
    mk_ = lambda i, *s: jax.random.normal(jax.random.fold_in(key, i), (k,) + s)
    d = 256 // scale
    return {
        "wq": mk_(0, d, d), "wk": mk_(1, d, 64), "wv": mk_(2, d, 64),
        "wo": mk_(3, d, d), "w_up": mk_(4, d, 4 * d),
        "w_down": mk_(5, 4 * d, d), "ln": mk_(6, d), "bias": mk_(7, d),
    }


def count_pallas_calls(fn, *args) -> int:
    """Number of pallas_call equations in fn's jaxpr (recursively)."""
    def walk(jaxpr) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    inner = v.jaxpr if hasattr(v.jaxpr, "eqns") else v
                    n += walk(inner)
        return n
    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


def _time(fn, *args, reps=5):
    # warm up with a single call and block on the held result (calling
    # twice -- once for an isinstance check, once discarded -- skewed
    # the first-rep cost before)
    out = fn(*args)
    if isinstance(out, tuple):
        out[0].block_until_ready()
    else:
        jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def traffic_audit(k: int, m: int, ns=(1, 8, 32), block_m: int = 256,
                  path: str = "single") -> dict:
    """One-residency audit via the kernel's own launch plan: input-block
    fetches and bytes must be N-independent at fixed tile sizes -- for
    either kernel path.  The two-pass audit additionally pins the
    modeled VMEM residency to the budget and the total modeled traffic
    to <= 2x the single-pass model at equal (K, M, N) (both paths
    stream the update tile once; the per-block stats stay in VMEM)."""
    plans = {n: mk.launch_plan(k, m, n, block_m=block_m, path=path)
             for n in ns}
    fetches = {n: p.input_block_fetches for n, p in plans.items()}
    in_bytes = {n: p.input_bytes for n, p in plans.items()}
    ok = len(set(fetches.values())) == 1 and len(set(in_bytes.values())) == 1
    assert ok, f"input stream depends on N: {fetches} / {in_bytes}"
    n_max = max(ns)
    audit = {
        "shape": f"K{k}_M{m}",
        "block_m": block_m,
        "path": path,
        "input_block_fetches_by_n": {str(n): fetches[n] for n in ns},
        "input_bytes_by_n": {str(n): in_bytes[n] for n in ns},
        "n_independent": ok,
        "vmem_bytes": max(p.vmem_bytes for p in plans.values()),
        # what the pre-batching (N, M, K) grid would have streamed at N_max
        "pre_fix_input_bytes_at_n_max": n_max * in_bytes[n_max],
        "traffic_reduction_at_n_max": n_max,
    }
    if path == "two_pass":
        ratio = max(
            plans[n].total_bytes
            / mk.launch_plan(k, m, n, block_m=block_m,
                             path="single").total_bytes
            for n in ns)
        assert ratio <= 2.0, f"two-pass traffic {ratio}x single-pass"
        assert audit["vmem_bytes"] <= mk.VMEM_BUDGET_BYTES, \
            f"two-pass VMEM model over budget: {audit['vmem_bytes']}"
        audit["total_bytes_vs_single_pass"] = round(ratio, 4)
        audit["single_pass_vmem_overflow"] = bool(
            mk.single_pass_vmem_bytes(k, max(ns), block_m)
            > mk.VMEM_BUDGET_BYTES)
    return audit


def _assert_finite(name: str, out) -> None:
    for leaf in jax.tree.leaves(out):
        assert bool(jnp.isfinite(leaf).all()), f"non-finite output: {name}"


def main(smoke: bool = False) -> tuple[list[tuple], list[dict], list[dict]]:
    shapes = SMOKE_SHAPES if smoke else SHAPES
    aggs = SMOKE_AGGS if smoke else AGGS
    reps = 2 if smoke else 5
    rows = []
    audits = []
    for k, m in shapes:
        x = jax.random.normal(jax.random.key(0), (k, m))
        x = x.at[-k // 4:].add(100.0)
        plan = mk.launch_plan(k, m, 1)
        fused_bytes = plan.input_bytes + plan.weight_bytes + plan.output_bytes
        for name in aggs:
            kw = {"num_malicious": k // 4} if name == "krum" else {}
            agg = aggregators.get_aggregator(name, **kw)
            f = jax.jit(lambda v, a=agg: a(v, None))
            us = _time(f, x, reps=reps)
            # derived: throughput in M coords / s
            rows.append((f"agg/{name}/K{k}_M{m}", us, m / us, None, 0))
        f = jax.jit(lambda v: ops.mm_aggregate(v))
        us = _time(f, x, reps=reps)
        rows.append((f"agg/mm_pallas_interp/K{k}_M{m}", us, m / us,
                     fused_bytes, 1))
        # weighted single-array kernel path (Eq. 13's a_k inside the kernel)
        a = jnp.linspace(0.5, 1.5, k)
        fw = jax.jit(lambda v, w: ops.mm_aggregate(v, w))
        us = _time(fw, x, a, reps=reps)
        rows.append((f"agg/mm_pallas_weighted/K{k}_M{m}", us, m / us,
                     fused_bytes, 1))
        # batched diffusion path: all N neighborhoods, one residency
        for n in (4,) if smoke else (8, 32):
            an = jax.random.uniform(jax.random.key(1), (k, n),
                                    minval=0.1, maxval=1.0)
            pn = mk.launch_plan(k, m, n)
            fb = jax.jit(
                lambda v, w: ops.mm_aggregate_batched(v, w))
            launches = count_pallas_calls(lambda v, w: ops.mm_aggregate_batched(v, w), x, an)
            assert launches == 1, launches
            us = _time(fb, x, an, reps=reps)
            rows.append((f"agg/mm_pallas_batched/K{k}_M{m}_N{n}", us,
                         n * m / us,
                         pn.input_bytes + pn.weight_bytes + pn.output_bytes,
                         launches))
        audits.append(traffic_audit(k, m))

    # large-cohort rows: the two-pass K-major kernel on meshes where
    # the single-pass VMEM plan overflows (the K=256 row is the ci.sh
    # smoke gate; non-finite output aborts the benchmark).  The audit
    # pins N-independent input bytes, modeled VMEM <= budget, and total
    # modeled traffic <= 2x the single-pass model for the same shape.
    for k, m in (SMOKE_LARGE_K_SHAPES if smoke else LARGE_K_SHAPES):
        x = jax.random.normal(jax.random.key(2), (k, m))
        x = x.at[-k // 4:].add(100.0)
        plan = mk.launch_plan(k, m, 1, path="two_pass")
        f2 = jax.jit(lambda v: ops.mm_aggregate(v, path="two_pass"))
        _assert_finite(f"mm_pallas_two_pass/K{k}_M{m}", f2(x))
        us = _time(f2, x, reps=reps)
        rows.append((f"agg/mm_pallas_two_pass/K{k}_M{m}", us, m / us,
                     plan.total_bytes, 1))
        audits.append(traffic_audit(k, m, block_m=128, path="two_pass"))

    # IRLS-depth sweep: us/call and MSD against a converged (T=50) jnp
    # oracle at fixed (K, M) -- the data behind the default T=10.
    k_i, m_i = (8, 1 << 12) if smoke else (32, 1 << 16)
    x_i = jax.random.normal(jax.random.key(3), (k_i, m_i))
    x_i = x_i.at[-k_i // 4:].add(100.0)
    converged = ref.mm_aggregate_ref(x_i, num_iters=50)
    irls_rows = []
    for t in IRLS_DEPTHS:
        ft = jax.jit(lambda v, _t=t: ops.mm_aggregate(v, num_iters=_t))
        out = ft(x_i)
        _assert_finite(f"irls_depth/T{t}", out)
        us = _time(ft, x_i, reps=reps)
        irls_rows.append({
            "num_iters": t,
            "shape": f"K{k_i}_M{m_i}",
            "us_per_call": round(us, 2),
            "msd_vs_oracle": float(jnp.mean((out - converged) ** 2)),
        })

    # scenario-runner path: one declarative spec -> a full scan'd run
    # per paradigm.  The runner AOT-compiles the scan before timing it,
    # so these rows are STEADY wall clock (compilation excluded by
    # construction); the compile cost is reported as its own
    # *_compile row so trajectory tooling never mixes the two.
    # BENCH_scenarios.json is the canonical per-spec record (it carries
    # compile_s and wall_clock_s side by side).
    from repro import scenarios
    sc = dict(num_agents=8, dim=8, num_steps=20, num_malicious=2,
              attack="additive") if smoke else \
        dict(num_agents=16, dim=10, num_steps=200, num_malicious=3,
             attack="additive")
    sc_backends = [("diffusion", "pallas"), ("federated", "jnp")] if smoke \
        else [("diffusion", "pallas"), ("diffusion", "jnp"),
              ("federated", "jnp"), ("sharded", "jnp")]
    for paradigm, backend in sc_backends:
        sp = scenarios.ScenarioSpec(paradigm=paradigm, backend=backend,
                                    aggregator="mm_tukey", **sc)
        res = scenarios.run(sp)
        coords = sc["num_steps"] * sc["num_agents"] * sc["dim"]
        tag = (f"{paradigm}/mm_tukey-{backend}"
               f"/K{sc['num_agents']}_M{sc['dim']}_T{sc['num_steps']}")
        us = res.wall_clock_s * 1e6
        rows.append((f"scenario_wall_steady/{tag}", us, coords / us, None, 0))
        rows.append((f"scenario_compile/{tag}", res.compile_s * 1e6, 0.0,
                     None, 0))

    # LM-substrate scenario: the spec drives launch.steps' robust train
    # step (per-agent grads -> stacked MM aggregation -> optimizer) in
    # the same scan; steady wall is per-train-step cost, jnp backend so
    # the row times the engine path rather than interpret-mode pallas.
    sub = scenarios.ScenarioSpec(
        paradigm="substrate", model_config="qwen3-0.6b",
        aggregator="mm_tukey", backend="jnp",
        num_agents=4 if smoke else 8, num_steps=2 if smoke else 10,
        num_malicious=1, attack="additive",
        paradigm_kwargs=(("batch_per_agent", 1),
                         ("seq_len", 8 if smoke else 16)))
    res = scenarios.run(sub)
    # coords = aggregated coordinates, consistent with every other row:
    # Mode A aggregates one full-parameter-sized stack per step
    n_params = sum(int(x.size) for x in jax.tree.leaves(res.final_state[0]))
    coords = sub.num_steps * n_params
    tag = (f"substrate[qwen3-0.6b]/mm_tukey-jnp"
           f"/K{sub.num_agents}_T{sub.num_steps}")
    rows.append((f"scenario_wall_steady/{tag}", res.wall_clock_s * 1e6,
                 coords / (res.wall_clock_s * 1e6), None, 0))
    rows.append((f"scenario_compile/{tag}", res.compile_s * 1e6, 0.0,
                 None, 0))

    # weighted-pytree engine path: the whole gradient tree in ONE launch
    for k in (8,) if smoke else (8, 32):
        tree = _grad_tree(k, scale=4 if smoke else 1)
        a = jnp.linspace(0.5, 1.5, k)
        n_leaves = len(jax.tree.leaves(tree))
        m_total = sum(int(l.size) // k for l in jax.tree.leaves(tree))
        eng = ops.AggregationEngine()
        launches = count_pallas_calls(
            lambda t, w: eng.aggregate_tree(t, w), tree, a)
        assert launches == 1, f"expected ONE kernel launch, got {launches}"
        pt = mk.launch_plan(k, m_total, 1)
        ft = jax.jit(lambda t, w: eng.aggregate_tree(t, w))
        us = _time(ft, tree, a, reps=reps)
        rows.append((f"agg/engine_tree_weighted/K{k}_leaves{n_leaves}"
                     f"_M{m_total}_launches{launches}", us, m_total / us,
                     pt.input_bytes + pt.weight_bytes + pt.output_bytes,
                     launches))
    return rows, audits, irls_rows


def write_json(path: str, rows, audits, irls_rows, smoke: bool) -> None:
    payload = {
        "bench": "agg",
        "mode": "smoke" if smoke else "full",
        "backend": jax.default_backend(),
        "rows": [
            {"name": name, "us_per_call": round(us, 2),
             "coords_per_us": round(thru, 6),
             "modeled_hbm_bytes": bytes_, "pallas_calls": calls}
            for name, us, thru, bytes_, calls in rows
        ],
        "traffic_audit": audits,
        "irls_sweep": irls_rows,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / few reps (ci.sh)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write BENCH_agg.json-style output")
    ns = ap.parse_args()
    compat.enable_persistent_compilation_cache()
    rows_, audits_, irls_ = main(smoke=ns.smoke)
    for name, us, thru, bytes_, calls in rows_:
        print(f"{name},{us:.2f},{thru:.6g}")
    for a_ in audits_:
        print(f"audit/{a_['shape']}[{a_['path']}]: fetches_by_n="
              f"{a_['input_block_fetches_by_n']} n_independent="
              f"{a_['n_independent']}")
    for r_ in irls_:
        print(f"irls/T{r_['num_iters']}: {r_['us_per_call']}us "
              f"msd_vs_oracle={r_['msd_vs_oracle']:.3g}")
    if ns.json:
        write_json(ns.json, rows_, audits_, irls_, ns.smoke)
        print(f"wrote {ns.json}")
