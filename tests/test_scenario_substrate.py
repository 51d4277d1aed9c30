"""Substrate paradigm (ScenarioSpec -> launch.steps train path) and the
runner's timing/metric/override bugfixes: bit-for-bit step parity,
per-layout launch audits vs the tuning cache, compile/wall separation,
spec-derived breakdown levels, and w0 validation."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scenarios
from repro.data import synthetic
from repro.kernels import tuning
from repro.launch import steps
from repro.scenarios import substrate

LM_TINY = dict(
    paradigm="substrate", model_config="qwen3-0.6b", aggregator="mm_tukey",
    num_agents=4, num_steps=2,
    paradigm_kwargs=(("batch_per_agent", 1), ("seq_len", 8)))


@pytest.fixture(autouse=True)
def _isolate_tuning_cache():
    saved = dict(tuning._CACHE)
    yield
    tuning._CACHE.clear()
    tuning._CACHE.update(saved)


# ===========================================================================
# tentpole: the substrate scan IS the launch.steps path
# ===========================================================================

def test_substrate_first_step_matches_launch_steps_bitwise():
    """The substrate adapter's first aggregated step reproduces the
    existing launch.steps path bit-for-bit on the same inputs: same
    model/optimizer build, same per-agent batch, same byzantine key
    chain, same aggregation resolution."""
    sp = scenarios.ScenarioSpec(
        seed=7, attack="additive", num_malicious=1, backend="jnp",
        **{**LM_TINY, "num_steps": 1,
           "paradigm_kwargs": (("batch_per_agent", 2), ("seq_len", 8))})
    res = scenarios.run(sp)
    params_scan, opt_scan = res.final_state

    model_cfg, par, opt_cfg, mesh, byz, (p0, o0), batch_fn = \
        substrate.build_lm_components(sp)
    step, _ = steps.make_train_step_gspmd(
        model_cfg, par, opt_cfg, mesh, byz, k_agents=sp.num_agents,
        consensus_metric=True)
    key0 = jax.random.split(jax.random.key(sp.seed), 1)[0]
    p1, o1, m = jax.jit(step)(p0, o0, batch_fn(key0))

    for a, b in zip(jax.tree.leaves(params_scan), jax.tree.leaves(p1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(res.history["loss"][0]) == float(m["loss"])
    assert float(res.history["consensus"][0]) == float(m["consensus"])


def test_substrate_lm_pallas_finite_with_per_layout_audit():
    """Pallas-backend substrate run: finite loss/consensus histories and
    a launch audit carrying one plan per aggregated tree layout, each
    with the block sizes the engine resolves for that workload."""
    sp = scenarios.ScenarioSpec(
        backend="pallas", attack="additive", num_malicious=1, **LM_TINY)
    res = scenarios.run(sp)
    assert res.finite()
    assert set(res.history) == {"msd", "loss", "consensus"}
    for h in res.history.values():
        assert h.shape == (sp.num_steps,)
    # training loss replaces the analytic msd (mirrored for summaries)
    np.testing.assert_array_equal(res.history["msd"], res.history["loss"])
    audit = res.launch_audit
    assert audit is not None and audit["n_layouts"] > 1
    for plan in audit["layouts"]:
        assert plan["n_out"] == 1            # Mode A aggregates per leaf
        assert plan["k_pad"] == sp.num_agents
        assert plan["block_m"] >= 128 and plan["grid"][0] >= 1
        # the grid's tiles cover the (R, C) view the kernel reads
        assert plan["m_total"] == plan["rows"] * plan["cols"]
        assert plan["grid"][0] * plan["block_rows"] * plan["block_cols"] \
            >= plan["m_total"]
        assert plan["view"] in ("rows", "flat")
    json_row = res.to_row()
    assert json_row["launch_audit"]["n_layouts"] == audit["n_layouts"]


def test_substrate_lsq_trains_and_mm_resists_attack():
    """paper_lsq substrate: the paper's linear problem trained through
    the launch.steps aggregation path.  MM keeps the training loss at
    the noise floor under the additive attack; mean breaks down."""
    base = dict(paradigm="substrate", model_config="paper_lsq",
                num_agents=8, dim=6, num_steps=150, step_size=0.05,
                attack="additive", num_malicious=2,
                attack_kwargs=(("delta", 100.0),))
    robust = scenarios.run(scenarios.ScenarioSpec(aggregator="mm_tukey",
                                                  **base))
    assert robust.finite()
    # settled to the irreducible noise floor sigma_v^2 / 2 = 0.005
    assert float(np.mean(robust.history["loss"][-30:])) < 0.05
    assert not robust.summary["broke_down"]

    broken = scenarios.run(scenarios.ScenarioSpec(aggregator="mean", **base))
    assert broken.summary["broke_down"]


def test_substrate_lsq_loss_grad_is_gradient_of_loss():
    prob = synthetic.LinearModelProblem(dim=5, noise_var=0.01, seed=0)
    fn = synthetic.make_stacked_loss_grad_fn(prob, 6)
    w = jax.random.normal(jax.random.key(1), (6, 5))
    key = jax.random.key(2)
    losses, grads = fn(w, key)
    auto = jax.grad(lambda ws: jnp.sum(fn(ws, key)[0]))(w)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(grads),
                               rtol=1e-5, atol=1e-6)


def test_substrate_schedules_and_backend_parity():
    """jnp and pallas backends agree on the substrate (identical
    estimator), with a time-varying malicious schedule in the loop."""
    base = dict(attack="sign_flip", num_malicious=1,
                attack_schedule="intermittent",
                schedule_kwargs=(("period", 1),), **LM_TINY)
    r_jnp = scenarios.run(scenarios.ScenarioSpec(backend="jnp", **base))
    r_pal = scenarios.run(scenarios.ScenarioSpec(backend="pallas", **base))
    assert r_jnp.finite() and r_pal.finite()
    np.testing.assert_allclose(r_jnp.history["loss"],
                               r_pal.history["loss"], rtol=1e-4, atol=1e-5)
    assert r_jnp.launch_audit is None and r_pal.launch_audit is not None


def test_substrate_spec_validation():
    with pytest.raises(ValueError, match="model_config"):
        scenarios.ScenarioSpec(paradigm="substrate")
    with pytest.raises(ValueError, match="unknown arch"):
        scenarios.ScenarioSpec(paradigm="substrate", model_config="gpt-17")
    with pytest.raises(ValueError, match="substrate-only"):
        scenarios.ScenarioSpec(paradigm="diffusion",
                               model_config="qwen3-0.6b")
    with pytest.raises(ValueError, match="aggregate_stack"):
        scenarios.ScenarioSpec(paradigm="substrate",
                               model_config="paper_lsq", aggregator="median")
    # LM token batches are iid; the dirichlet knob must not be a silent
    # no-op (paper_lsq DOES model it, so it stays allowed there)
    with pytest.raises(ValueError, match="iid"):
        scenarios.ScenarioSpec(paradigm="substrate",
                               model_config="qwen3-0.6b", data="dirichlet")
    scenarios.ScenarioSpec(paradigm="substrate", model_config="paper_lsq",
                           data="dirichlet")


def test_scenarios_import_stays_light():
    """Importing repro.scenarios must not pull the training stack; the
    substrate paradigm is registered lazily by the runner."""
    import subprocess as sp_
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"      # a child never competes for a chip
    out = sp_.run([sys.executable, "-c",
                   "import sys, repro.scenarios; "
                   "assert 'repro.models.model' not in sys.modules; "
                   "assert 'repro.scenarios.substrate' not in sys.modules; "
                   "print('light')"],
                  env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-1500:]


def test_grad_consensus_semantics():
    benign = jnp.array([True, True, True, False])
    same = {"a": jnp.ones((4, 3)), "b": jnp.zeros((4, 2, 2))}
    assert float(steps.grad_consensus(same, benign)) == 0.0
    spread = {"a": same["a"].at[0].add(1.0), "b": same["b"]}
    assert float(steps.grad_consensus(spread, benign)) > 0.0
    # the malicious row does not contribute
    poisoned = {"a": same["a"].at[3].add(1e6), "b": same["b"]}
    assert float(steps.grad_consensus(poisoned, benign)) == 0.0


# ===========================================================================
# satellite: compile_s / wall_clock_s separation
# ===========================================================================

def test_wall_clock_excludes_compile():
    """Steady wall clock is measured on the already-AOT-compiled scan:
    for a tiny problem the (always non-zero) compile cost dominates the
    steady run by construction, and both ride into the BENCH row."""
    sp = scenarios.ScenarioSpec(paradigm="diffusion", aggregator="mm_tukey",
                                num_agents=8, dim=6, num_steps=10)
    res = scenarios.run(sp)
    assert res.compile_s > 0.0 and res.wall_clock_s > 0.0
    assert res.compile_s > res.wall_clock_s, (
        "steady wall clock must not include XLA compilation",
        res.compile_s, res.wall_clock_s)
    row = res.to_row()
    assert {"compile_s", "wall_clock_s"} <= set(row)
    assert row["compile_s"] > row["wall_clock_s"]


# ===========================================================================
# satellite: spec-derived breakdown level
# ===========================================================================

def test_breakdown_threshold_fixes_both_misclassifications():
    # direction 1: a slow clean run (tiny mu) whose trailing mean is
    # still above the old hard-wired 1.0 must NOT be flagged
    slow = scenarios.ScenarioSpec(paradigm="diffusion", aggregator="mean",
                                  step_size=1e-4, num_steps=50)
    level = scenarios.breakdown_threshold(slow)
    assert level > 1.0
    still_descending = np.linspace(1.3, 1.05, 50)
    assert scenarios.attack_summary(still_descending)["broke_down"]  # old
    assert not scenarios.attack_summary(
        still_descending, breakdown_level=level)["broke_down"]       # fixed

    # direction 2: an attacked run wedged far above its clean steady
    # state but below 1.0 MUST be flagged
    fast = scenarios.ScenarioSpec(paradigm="diffusion", aggregator="mm_tukey",
                                  step_size=0.05, num_steps=400)
    level = scenarios.breakdown_threshold(fast)
    assert level < 0.5
    wedged = np.full(400, 0.5)
    assert not scenarios.attack_summary(wedged)["broke_down"]        # old
    assert scenarios.attack_summary(
        wedged, breakdown_level=level)["broke_down"]                 # fixed


def test_runner_summary_uses_derived_level():
    sp = scenarios.ScenarioSpec(paradigm="diffusion", aggregator="mm_tukey",
                                num_agents=8, dim=6, num_steps=12)
    res = scenarios.run(sp)
    assert res.summary["breakdown_level"] == pytest.approx(
        scenarios.breakdown_threshold(sp))


# ===========================================================================
# satellite: w0 override validation
# ===========================================================================

def test_w0_override_validated_not_broadcast():
    sp = scenarios.ScenarioSpec(paradigm="diffusion", aggregator="mean",
                                num_agents=8, dim=6, num_steps=5)
    # wrong shape: a (M,) vector against the (K, M) stacked state used
    # to broadcast silently -- must raise with a clear message now
    with pytest.raises(ValueError, match="shape"):
        scenarios.run(sp, w0=np.zeros(6))
    with pytest.raises(ValueError, match="structure"):
        scenarios.run(sp, w0={"oops": np.zeros((8, 6))})
    # right shape works (and f64 input is cast to the adapter's dtype)
    good = scenarios.run(sp, w0=np.full((8, 6), 0.5))
    assert good.finite()
    base = scenarios.run(sp)
    assert not np.array_equal(good.history["msd"], base.history["msd"])


def test_w0_override_validated_for_single_model_paradigms():
    sp = scenarios.ScenarioSpec(paradigm="federated", aggregator="mean",
                                num_agents=8, dim=6, num_steps=5)
    with pytest.raises(ValueError, match="shape"):
        scenarios.run(sp, w0=np.zeros((8, 6)))
    assert scenarios.run(sp, w0=np.zeros(6)).finite()


# ===========================================================================
# satellite: launch audit vs the engine's actual block selection
# ===========================================================================

def test_audit_matches_tuning_cache_winner(tmp_path, monkeypatch):
    """When REPRO_TUNING_CACHE holds a winner, the audited launch_plan
    geometry must be the block choice the engine actually selected --
    for both the diffusion (batched N) and federated (N=1) shapes."""
    k, m = 8, 8
    clients = 4
    tuning.set_blocks(k, m, k, jnp.float32, (256, None))        # diffusion
    tuning.set_blocks(clients, m, 1, jnp.float32, (256, None))  # federated
    path = str(tmp_path / "tune.json")
    assert tuning.save_cache(path) == path
    tuning.clear_cache()
    monkeypatch.setenv(tuning.ENV_CACHE_PATH, path)
    monkeypatch.setattr(tuning, "_persistent_loaded", False)

    diff = scenarios.run(scenarios.ScenarioSpec(
        paradigm="diffusion", aggregator="mm_tukey", backend="pallas",
        num_agents=k, dim=m, num_steps=4))
    a = diff.launch_audit
    assert a["n_out"] == k and a["k_pad"] == k
    # the cross-process winner, not the 128-lane heuristic the un-cached
    # shape would resolve to
    assert a["block_m"] == 256
    assert tuning.heuristic_blocks(k, m, k)[0] != 256

    fed = scenarios.run(scenarios.ScenarioSpec(
        paradigm="federated", aggregator="mm_tukey", backend="pallas",
        num_agents=k, participation=0.5, num_steps=4, dim=m))
    a = fed.launch_audit
    # reality check: the federated aggregation runs over the sampled
    # cohort (clients_per_round rows), and that is what gets audited
    assert a["n_out"] == 1 and a["k_pad"] == clients
    assert a["block_m"] == 256


def test_audit_heuristic_when_cache_empty():
    res = scenarios.run(scenarios.ScenarioSpec(
        paradigm="diffusion", aggregator="mm_tukey", backend="pallas",
        num_agents=8, dim=8, num_steps=3))
    a = res.launch_audit
    bm, bk = tuning.get_blocks(8, 8, 8)
    assert a["block_m"] == bm


# ===========================================================================
# CLI surfaces
# ===========================================================================

def test_scenario_sweep_substrate_smoke_cli():
    """The acceptance command: a pallas-backend substrate spec end to
    end through the sweep CLI, exiting 0 with finite metrics."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"      # a child never competes for a chip
    out = subprocess.run(
        [sys.executable, os.path.join(root, "examples", "scenario_sweep.py"),
         "--paradigm", "substrate", "--smoke"],
        cwd=root, env=env, capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:] + out.stdout[-2000:]
    assert "all metrics finite" in out.stdout
    assert "substrate[qwen3-0.6b]" in out.stdout
    assert "yes" in out.stdout   # audit attached (pallas default)


def test_launch_train_scenario_mode_runs():
    """launch.train --scenario drives the run through the ScenarioSpec."""
    from repro.launch import train
    losses = train.main([
        "--scenario", "--arch", "qwen3-0.6b", "--steps", "2",
        "--batch", "4", "--agents", "4", "--seq", "8",
        "--malicious", "1", "--log-every", "1"])
    assert len(losses) == 2
    assert all(np.isfinite(losses))
