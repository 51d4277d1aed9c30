"""Scenario subsystem: spec lowering parity, adapters, attacks e2e,
schedules, topologies, heterogeneity, and tuning-cache persistence."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scenarios
from repro.core import attacks, diffusion, federated, graph
from repro.data import synthetic
from repro.kernels import tuning

K, DIM = 8, 6
TINY = dict(num_agents=K, dim=DIM, num_steps=15, step_size=0.05)


# ===========================================================================
# parity: one spec reproduces the legacy wrappers bit-for-bit
# ===========================================================================

def test_diffusion_spec_matches_wrapper_bitwise():
    sp = scenarios.ScenarioSpec(
        paradigm="diffusion", aggregator="mm_tukey", attack="additive",
        num_malicious=2, attack_kwargs=(("delta", 100.0),), seed=3, **TINY)
    res = scenarios.run(sp)

    prob = synthetic.LinearModelProblem(dim=DIM, noise_var=0.01, seed=0)
    comb = graph.uniform_weights(graph.fully_connected(K))
    cfg = diffusion.DiffusionConfig(
        step_size=0.05, aggregator="mm_tukey",
        byzantine=attacks.ByzantineConfig(
            num_malicious=2, attack="additive",
            attack_kwargs=(("delta", 100.0),)))
    _, hist = diffusion.run_diffusion(
        grad_fn=prob.grad_fn(), combination=comb, config=cfg,
        w_star=prob.w_star, num_iters=15, key=jax.random.key(3))
    assert np.array_equal(np.asarray(hist), res.history["msd"])


def test_federated_spec_matches_wrapper_bitwise():
    sp = scenarios.ScenarioSpec(
        paradigm="federated", aggregator="mm_tukey", participation=0.5,
        local_steps=3, num_malicious=2, seed=5, **TINY)
    res = scenarios.run(sp)

    prob = synthetic.LinearModelProblem(dim=DIM, noise_var=0.01, seed=0)
    grad_fn = synthetic.make_client_grad_fn(prob, K)
    cfg = federated.FederatedConfig(
        num_clients=K, clients_per_round=4, local_steps=3, step_size=0.05,
        aggregator="mm_tukey",
        byzantine=attacks.ByzantineConfig(num_malicious=2))
    _, hist = federated.run_federated(
        grad_fn=grad_fn, config=cfg, w_star=prob.w_star, num_rounds=15,
        key=jax.random.key(5))
    assert np.array_equal(np.asarray(hist), res.history["msd"])


def test_pallas_backend_matches_jnp_backend():
    base = dict(paradigm="diffusion", aggregator="mm_tukey",
                num_malicious=2, **TINY)
    r_jnp = scenarios.run(scenarios.ScenarioSpec(backend="jnp", **base))
    r_pal = scenarios.run(scenarios.ScenarioSpec(backend="pallas", **base))
    np.testing.assert_allclose(
        r_jnp.history["msd"], r_pal.history["msd"], rtol=1e-5, atol=1e-7)
    assert r_pal.launch_audit is not None
    assert r_jnp.launch_audit is None
    # diffusion's batched kernel carries all K neighborhood columns
    assert r_pal.launch_audit["n_out"] == K


# ===========================================================================
# result structure / metrics
# ===========================================================================

def test_result_uniform_history_and_summary():
    sp = scenarios.ScenarioSpec(paradigm="diffusion", aggregator="mean",
                                **TINY)
    res = scenarios.run(sp)
    assert set(res.history) == {"msd", "loss", "consensus"}
    for h in res.history.values():
        assert h.shape == (sp.num_steps,)
    np.testing.assert_allclose(
        res.history["loss"], res.history["msd"] + sp.noise_var)
    assert {"steady_msd", "peak_msd", "broke_down"} <= set(res.summary)
    assert res.finite()
    assert not res.summary["broke_down"]
    row = res.to_row()
    json.dumps(row)   # BENCH row must be JSON-able
    assert row["paradigm"] == "diffusion"


def test_attack_summary_flags_breakdown():
    sp = scenarios.ScenarioSpec(
        paradigm="diffusion", aggregator="mean", attack="additive",
        num_malicious=2, attack_kwargs=(("delta", 1000.0),),
        **{**TINY, "num_steps": 60})
    res = scenarios.run(sp)
    assert res.summary["broke_down"]

    robust = scenarios.run(scenarios.ScenarioSpec(
        paradigm="diffusion", aggregator="mm_tukey", attack="additive",
        num_malicious=2, attack_kwargs=(("delta", 1000.0),),
        **{**TINY, "num_steps": 60}))
    assert not robust.summary["broke_down"]


def test_consensus_distance_zero_at_consensus():
    w = jnp.ones((4, 3))
    benign = jnp.array([True, True, True, False])
    from repro.scenarios import metrics
    assert float(metrics.consensus_distance(w, benign)) == 0.0
    w2 = w.at[0].add(1.0)
    assert float(metrics.consensus_distance(w2, benign)) > 0.0


# ===========================================================================
# spec validation and registry
# ===========================================================================

def test_spec_validation_errors():
    with pytest.raises(ValueError, match="paradigm"):
        scenarios.ScenarioSpec(paradigm="gossip")
    with pytest.raises(ValueError, match="pallas"):
        scenarios.ScenarioSpec(aggregator="mean", backend="pallas")
    with pytest.raises(ValueError, match="participation"):
        scenarios.ScenarioSpec(paradigm="diffusion", participation=0.5)
    with pytest.raises(ValueError, match="topology"):
        scenarios.ScenarioSpec(topology="moebius")
    with pytest.raises(ValueError, match="attack"):
        scenarios.ScenarioSpec(attack="nope")
    with pytest.raises(ValueError, match="schedule"):
        scenarios.ScenarioSpec(attack_schedule="sometimes")
    with pytest.raises(ValueError, match="num_malicious"):
        scenarios.ScenarioSpec(num_agents=4, num_malicious=4)


def test_spec_is_hashable_and_resolves_backend():
    sp = scenarios.ScenarioSpec(aggregator="mm_tukey", backend="pallas")
    hash(sp)
    assert sp.resolved_aggregator()[0] == "mm_pallas"
    assert scenarios.ScenarioSpec(
        aggregator="mm_pallas", backend="jnp").resolved_aggregator()[0] \
        == "mm_tukey"


def test_register_custom_paradigm_runs_through_runner():
    @scenarios.register_paradigm("constant_drift")
    def _adapter(spec):
        w0 = jnp.zeros((spec.dim,))

        def step(w, key, i):
            w_next = w + spec.step_size
            return w_next, {"msd": jnp.sum(w_next ** 2),
                            "consensus": jnp.zeros(())}
        return w0, step

    assert "constant_drift" in scenarios.paradigm_names()
    sp = scenarios.ScenarioSpec(paradigm="constant_drift", aggregator="mean",
                                **TINY)
    res = scenarios.run(sp)
    assert res.history["msd"].shape == (sp.num_steps,)
    assert res.finite()


# ===========================================================================
# attacks: registry completeness, e2e through both adapters
# ===========================================================================

@pytest.mark.parametrize("name", attacks.names())
def test_attack_registry_semantics_under_jit(name):
    """Every registered attack, jitted: honest rows untouched, corrupted
    rows differ from the honest values."""
    key = jax.random.key(0)
    x = jax.random.normal(key, (6, 5))
    mask = jnp.arange(6) >= 4
    fn = jax.jit(attacks.get_attack(name))
    out = fn(x, mask, jax.random.key(1), 0)
    assert out.shape == x.shape
    assert jnp.isfinite(out).all(), name
    np.testing.assert_array_equal(np.asarray(out[:4]), np.asarray(x[:4]))
    assert np.abs(np.asarray(out[4:]) - np.asarray(x[4:])).max() > 1e-6, name


@pytest.mark.parametrize("paradigm", ["federated", "diffusion"])
@pytest.mark.parametrize("name", attacks.names())
def test_attack_registry_end_to_end(paradigm, name):
    """Every registered attack runs under jit through the federated and
    diffusion adapters with a nonzero malicious mask, finite metrics."""
    sp = scenarios.ScenarioSpec(
        paradigm=paradigm, aggregator="mm_tukey", attack=name,
        num_malicious=2, **{**TINY, "num_steps": 6})
    res = scenarios.run(sp)
    assert res.finite(), (paradigm, name)


def test_scm_sits_inside_acceptance_region():
    """SCM corrupted rows sit at median + zeta*c*MADN of the benign
    rows: maximal accepted perturbation, per coordinate."""
    key = jax.random.key(0)
    x = jax.random.normal(key, (16, 7))
    mask = jnp.arange(16) >= 12
    zeta, c = 0.9, 4.685
    out = attacks.scm(x, mask, None, 0, zeta=zeta, c=c)
    b = np.asarray(x[:12])
    med = np.median(b, axis=0)
    madn = np.median(np.abs(b - med), axis=0) * 1.4826
    corrupted = np.asarray(out[12:])
    # all corrupted rows identical (collusion) and within the region
    assert np.abs(corrupted - corrupted[0]).max() == 0.0
    dev = np.abs(corrupted[0] - med)
    assert (dev <= c * madn + 1e-5).all()
    assert (dev >= 0.5 * c * madn).all()


# ===========================================================================
# time-varying malicious-mask schedules
# ===========================================================================

def test_intermittent_schedule_toggles():
    byz = attacks.ByzantineConfig(
        num_malicious=2, schedule="intermittent",
        schedule_kwargs=(("period", 3),))
    on = np.asarray(byz.malicious_mask(6, 0))
    off = np.asarray(byz.malicious_mask(6, 3))
    assert on.sum() == 2 and off.sum() == 0
    np.testing.assert_array_equal(on, np.asarray(byz.malicious_mask(6, 1)))
    # static ignores the step entirely
    st = attacks.ByzantineConfig(num_malicious=2)
    np.testing.assert_array_equal(
        np.asarray(st.malicious_mask(6, 0)), np.asarray(st.malicious_mask(6, 99)))


def test_rotating_schedule_moves_the_set():
    byz = attacks.ByzantineConfig(
        num_malicious=1, schedule="rotating", schedule_kwargs=(("period", 1),))
    m0 = np.asarray(byz.malicious_mask(4, 0))
    m1 = np.asarray(byz.malicious_mask(4, 1))
    assert m0.sum() == m1.sum() == 1
    assert m0.argmax() == 3 and m1.argmax() == 0   # rolled by one


def test_scheduled_scenario_runs_jitted():
    sp = scenarios.ScenarioSpec(
        paradigm="diffusion", aggregator="mm_tukey", attack="additive",
        num_malicious=2, attack_schedule="intermittent",
        schedule_kwargs=(("period", 2),), **TINY)
    res = scenarios.run(sp)
    assert res.finite()


# ===========================================================================
# topologies
# ===========================================================================

def test_small_world_is_connected_symmetric_ring_limit():
    adj = graph.small_world(12, nbrs=2, rewire_p=0.3, seed=1)
    assert graph.is_connected(adj)
    np.testing.assert_array_equal(adj, adj.T)
    assert adj.diagonal().all()
    np.testing.assert_array_equal(
        graph.small_world(12, nbrs=2, rewire_p=0.0), graph.ring(12, hops=2))


def test_star_topology():
    adj = graph.star(6)
    assert adj[0].all() and adj[:, 0].all()
    assert adj.sum() == 6 + 2 * 5   # self loops + hub spokes
    comb = graph.combination_matrix(adj, "metropolis")
    graph.validate_combination_matrix(comb)


def test_topology_registry():
    for name in graph.topology_names():
        adj = graph.get_topology(name, 9)
        assert adj.shape == (9, 9) and graph.is_connected(adj)
    with pytest.raises(ValueError, match="topology"):
        graph.get_topology("torus", 9)
    # grid accepts a pinned factorization, rejects a non-divisor
    adj = graph.get_topology("grid", 12, rows=3)
    assert adj.shape == (12, 12) and graph.is_connected(adj)
    with pytest.raises(ValueError, match="rows"):
        graph.get_topology("grid", 12, rows=5)


def test_effective_topology_in_rows():
    sp = scenarios.ScenarioSpec(paradigm="federated", topology="ring")
    assert sp.effective_topology() == "star"
    assert "/star/" in sp.label()
    sp2 = scenarios.ScenarioSpec(paradigm="diffusion", topology="ring")
    assert sp2.effective_topology() == "ring"


def test_to_row_is_strict_json_even_when_broken_down():
    sp = scenarios.ScenarioSpec(
        paradigm="diffusion", aggregator="mean", attack="scale",
        num_malicious=2, attack_kwargs=(("gamma", 1e18),),
        **{**TINY, "num_steps": 40})
    res = scenarios.run(sp)
    row = res.to_row()
    json.dumps(row, allow_nan=False)   # no Infinity/NaN tokens
    if not res.finite():
        assert row["final_msd"] is None


@pytest.mark.parametrize("topology", ["ring", "small_world", "star",
                                      "erdos_renyi", "grid"])
def test_diffusion_runs_on_every_topology(topology):
    sp = scenarios.ScenarioSpec(
        paradigm="diffusion", aggregator="mm_tukey", topology=topology,
        **{**TINY, "num_steps": 8})
    assert scenarios.run(sp).finite()


# ===========================================================================
# data heterogeneity
# ===========================================================================

def test_dirichlet_mixture_shapes_and_validation():
    pi, scales = synthetic.dirichlet_mixture(10, 0.5, num_components=4)
    assert pi.shape == (10, 4) and scales.shape == (4,)
    np.testing.assert_allclose(pi.sum(axis=1), 1.0, atol=1e-9)
    with pytest.raises(ValueError, match="alpha"):
        synthetic.dirichlet_mixture(10, 0.0)


def test_dirichlet_split_is_heterogeneous_but_unbiased():
    prob = synthetic.LinearModelProblem(dim=5, noise_var=0.0, seed=0)
    fn = synthetic.make_stacked_grad_fn(prob, 16, data="dirichlet",
                                        alpha=0.1, seed=0)
    # gradient at w_star has zero mean (unbiasedness survives the split)
    w = jnp.broadcast_to(prob.w_star, (16, 5))
    g = np.stack([np.asarray(fn(w, jax.random.key(i))) for i in range(300)])
    assert np.abs(g.mean(axis=0)).max() < 0.2
    # per-agent gradient scale differs across agents (non-iid covariance)
    at_zero = jnp.zeros((16, 5))
    n = np.stack([np.linalg.norm(np.asarray(fn(at_zero, jax.random.key(i))),
                                 axis=1) for i in range(300)]).mean(axis=0)
    assert n.max() / n.min() > 1.3


@pytest.mark.parametrize("paradigm", ["federated", "diffusion", "sharded"])
def test_dirichlet_scenarios_run(paradigm):
    sp = scenarios.ScenarioSpec(
        paradigm=paradigm, aggregator="mm_tukey", data="dirichlet",
        dirichlet_alpha=0.3, num_malicious=2,
        **{**TINY, "num_steps": 8})
    assert scenarios.run(sp).finite()


# ===========================================================================
# sharded paradigm
# ===========================================================================

def test_sharded_stacked_path_converges_and_is_robust():
    clean = scenarios.run(scenarios.ScenarioSpec(
        paradigm="sharded", aggregator="mm_tukey",
        **{**TINY, "num_steps": 200}))
    assert clean.history["msd"][-1] < 1e-2
    attacked = scenarios.run(scenarios.ScenarioSpec(
        paradigm="sharded", aggregator="mm_tukey", attack="additive",
        num_malicious=2, attack_kwargs=(("delta", 1000.0),),
        **{**TINY, "num_steps": 200}))
    assert attacked.history["msd"][-1] < 5e-2


def test_sharded_collective_matches_stacked():
    """The shard_map lowering (core.sharded.robust_all_reduce, the
    robust-FSDP building block) reproduces the stacked single-program
    run.  8 forced host devices in a subprocess (device count locks at
    first jax init)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import numpy as np
        from repro import scenarios
        base = dict(paradigm="sharded", aggregator="mm_tukey",
                    num_agents=8, dim=6, num_steps=25, step_size=0.05,
                    attack="additive", num_malicious=2)
        stacked = scenarios.run(scenarios.ScenarioSpec(**base))
        coll = scenarios.run(scenarios.ScenarioSpec(
            paradigm_kwargs=(("collective", "rs_mm"),), **base))
        print(json.dumps({
            "max_diff": float(np.abs(stacked.history["msd"]
                                     - coll.history["msd"]).max()),
            "finite": bool(coll.finite()),
        }))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"      # a child never competes for a chip
    out = subprocess.run([sys.executable, "-c", script], cwd=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["finite"]
    assert res["max_diff"] < 1e-5, res


# ===========================================================================
# tuning cache persistence
# ===========================================================================

@pytest.fixture(autouse=True)
def _isolate_tuning_cache():
    saved = dict(tuning._CACHE)
    yield
    tuning._CACHE.clear()
    tuning._CACHE.update(saved)


def test_tuning_cache_roundtrip(tmp_path):
    path = str(tmp_path / "tune.json")
    tuning.set_blocks(7, 999, 2, jnp.float32, (256, 8))
    assert tuning.save_cache(path) == path
    tuning.clear_cache()
    assert tuning.get_blocks(7, 999, 2) == tuning.heuristic_blocks(7, 999, 2)
    assert tuning.load_cache(path) == 1
    assert tuning.get_blocks(7, 999, 2) == (256, 8)
    # file is valid JSON with the (K, M, N, dtype, backend) key schema
    with open(path) as f:
        payload = json.load(f)
    e = payload["entries"][0]
    assert {"k", "m", "n", "dtype", "backend", "block_m", "block_k"} \
        <= set(e)


def test_tuning_cache_corrupt_file_falls_back(tmp_path):
    path = str(tmp_path / "corrupt.json")
    with open(path, "w") as f:
        f.write("{not json at all")
    tuning.clear_cache()
    assert tuning.load_cache(path) == 0
    assert tuning.get_blocks(7, 999, 2) == tuning.heuristic_blocks(7, 999, 2)
    # wrong schema is also tolerated
    with open(path, "w") as f:
        json.dump({"entries": [{"k": "x"}]}, f)
    assert tuning.load_cache(path) == 0


def test_tuning_cache_in_process_wins(tmp_path):
    path = str(tmp_path / "tune.json")
    tuning.set_blocks(5, 500, 1, jnp.float32, (128, None))
    tuning.save_cache(path)
    tuning.clear_cache()
    tuning.set_blocks(5, 500, 1, jnp.float32, (512, None))
    tuning.load_cache(path)
    assert tuning.get_blocks(5, 500, 1) == (512, None)


def test_tuning_cache_partial_corruption_keeps_valid_entries(tmp_path):
    path = str(tmp_path / "partial.json")
    with open(path, "w") as f:
        json.dump({"entries": [
            {"k": 5, "m": 500, "n": 1, "dtype": "float32",
             "backend": "pallas", "block_m": 256, "block_k": None},
            {"k": "garbage"},
            {"k": 6, "m": 600, "n": 1, "dtype": "float32",
             "backend": "pallas", "block_m": 128, "block_k": None},
        ]}, f)
    tuning.clear_cache()
    assert tuning.load_cache(path) == 2   # malformed entry skipped, rest kept
    assert tuning.get_blocks(5, 500, 1) == (256, None)
    assert tuning.get_blocks(6, 600, 1) == (128, None)


def test_explicit_load_does_not_suppress_env_merge(tmp_path, monkeypatch):
    env_path = str(tmp_path / "env.json")
    tuning.set_blocks(5, 501, 1, jnp.float32, (256, None))
    tuning.save_cache(env_path)
    tuning.clear_cache()
    monkeypatch.setenv(tuning.ENV_CACHE_PATH, env_path)
    monkeypatch.setattr(tuning, "_persistent_loaded", False)
    # an explicit-path load (missing file) must not mark the env cache
    # as already merged
    tuning.load_cache(str(tmp_path / "missing.json"))
    assert tuning.get_blocks(5, 501, 1) == (256, None)


def test_tuning_cache_env_path(tmp_path, monkeypatch):
    path = str(tmp_path / "env_tune.json")
    monkeypatch.setenv(tuning.ENV_CACHE_PATH, path)
    tuning.set_blocks(9, 256, 1, jnp.float32, (128, None))
    assert tuning.save_cache() == path
    assert os.path.exists(path)


# ===========================================================================
# satellite: scenario-runner executable cache
# ===========================================================================

def test_second_run_of_identical_spec_hits_compile_cache():
    """A repeated run of the exact same spec must reuse the compiled
    scan (compile_cache_hit, compile_s == 0) with identical results,
    identical launch audit, and a steady wall clock that is still a
    real measurement of the same program."""
    from repro.scenarios import runner

    sp = scenarios.ScenarioSpec(paradigm="diffusion", aggregator="mm_tukey",
                                backend="pallas", num_malicious=2,
                                num_agents=K, dim=DIM, num_steps=9)
    runner.clear_executable_cache()
    try:
        r1 = scenarios.run(sp)
        r2 = scenarios.run(sp)
        assert not r1.compile_cache_hit and r1.compile_s > 0.0
        assert r2.compile_cache_hit and r2.compile_s == 0.0
        assert r2.wall_clock_s > 0.0
        for name in r1.history:
            np.testing.assert_array_equal(r1.history[name],
                                          r2.history[name])
        assert r1.launch_audit == r2.launch_audit
        row = r2.to_row()
        assert row["compile_cache_hit"] is True
        # a *different* spec is a miss
        r3 = scenarios.run(
            scenarios.ScenarioSpec(paradigm="diffusion",
                                   aggregator="mm_tukey", backend="pallas",
                                   num_malicious=2, num_agents=K, dim=DIM,
                                   num_steps=8))
        assert not r3.compile_cache_hit
    finally:
        runner.clear_executable_cache()


def test_executable_cache_keys_on_tuning_state():
    """A new tuning winner changes the kernel geometry the compiled
    program bakes in: the executable cache must miss, recompile, and
    audit the new geometry."""
    from repro.scenarios import runner

    sp = scenarios.ScenarioSpec(paradigm="diffusion", aggregator="mm_tukey",
                                backend="pallas", num_agents=K, dim=DIM,
                                num_steps=7)
    runner.clear_executable_cache()
    tuning.clear_cache()
    try:
        r1 = scenarios.run(sp)
        tuning.set_blocks(K, DIM, K, jnp.float32, (256, None))
        r2 = scenarios.run(sp)
        assert not r2.compile_cache_hit, \
            "tuning-state change must invalidate the executable cache"
        assert r2.launch_audit["block_m"] == 256
        assert r1.launch_audit["block_m"] != 256
    finally:
        tuning.clear_cache()
        runner.clear_executable_cache()


def test_w0_override_reuses_cached_executable():
    from repro.scenarios import runner

    sp = scenarios.ScenarioSpec(paradigm="federated", aggregator="mm_tukey",
                                num_agents=K, dim=DIM, num_steps=6)
    runner.clear_executable_cache()
    try:
        r1 = scenarios.run(sp)
        r2 = scenarios.run(sp, w0=np.ones(DIM, np.float32))
        assert r2.compile_cache_hit     # same avals, same program
        assert not np.array_equal(r1.history["msd"], r2.history["msd"])
        assert r2.finite()
    finally:
        runner.clear_executable_cache()
