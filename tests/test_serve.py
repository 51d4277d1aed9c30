"""The streaming aggregation service: admission buffer, degradation
ladder, executable cache, fault injection, chaos replay, and the
serve-side audit rules (with mutation fixtures proving the auditors
catch the defect classes they exist for)."""

import collections

import numpy as np
import pytest

from repro.analysis import bench_audit, jaxpr_audit
from repro.scenarios import metrics
from repro.scenarios.spec import ScenarioSpec
from repro.serve import buffer as sbuf
from repro.serve import chaos as schaos
from repro.serve import retry as sretry
from repro.serve import scenario as sscenario
from repro.serve import service as ssvc
from repro.serve.clock import SimClock

DIM = 6


def upd(agent, *, round=0, seq=1, value=1.0, weight=1.0, payload=None):
    if payload is None:
        payload = np.full(DIM, value, np.float32)
    return sbuf.AgentUpdate(agent_id=agent, round=round, payload=payload,
                            weight=weight, seq=seq)


def make_service(**cfg_kw):
    defaults = dict(k_min=4, quorum=2, deadline_s=1.0, backend="jnp",
                    max_staleness=4)
    defaults.update(cfg_kw)
    clock = SimClock()
    svc = ssvc.AggregationService(
        np.zeros(DIM, np.float32),
        config=ssvc.ServeConfig(**defaults), clock=clock)
    return svc, clock


def fill_full_cohort(svc, *, value=0.5, round=None, seq=1):
    """Submit one full cohort of identical honest updates."""
    r = svc.round if round is None else round
    for agent in range(svc.config.k_min):
        svc.submit(upd(agent, round=r, seq=seq, value=value))


# ===========================================================================
# admission buffer
# ===========================================================================

def test_buffer_verdicts():
    b = sbuf.CohortBuffer(max_staleness=2, max_buffer=3)
    assert b.add(upd(0, seq=1), now=0.0, current_round=0) == "buffered"
    # same seq again: replayed delivery
    assert b.add(upd(0, seq=1), now=0.1, current_round=0) == "duplicate"
    # newer seq from the same agent replaces the pending slot
    assert b.add(upd(0, seq=2, value=2.0), now=0.2,
                 current_round=0) == "superseded"
    assert len(b) == 1
    # beyond the staleness window
    assert b.add(upd(1, round=0, seq=1), now=0.3,
                 current_round=3) == "rejected_stale"
    # ...and its seq was consumed: the replay stays dead
    assert b.add(upd(1, round=3, seq=1), now=0.4,
                 current_round=3) == "duplicate"
    # non-finite payload never becomes pending
    bad = np.full(DIM, np.nan, np.float32)
    assert b.add(upd(2, seq=1, payload=bad), now=0.5,
                 current_round=0) == "rejected_invalid"
    # backpressure at capacity (0 and two newcomers fill max_buffer=3)
    assert b.add(upd(3, seq=1), now=0.6, current_round=0) == "buffered"
    assert b.add(upd(4, seq=1), now=0.7, current_round=0) == "buffered"
    assert b.add(upd(5, seq=1), now=0.8, current_round=0) == "rejected_full"


def test_buffer_take_is_fifo_by_arrival():
    b = sbuf.CohortBuffer()
    for i, t in ((3, 0.3), (1, 0.1), (2, 0.2)):
        b.add(upd(i, seq=1), now=t, current_round=0)
    taken = b.take(2)
    assert [p.update.agent_id for p in taken] == [1, 2]
    assert len(b) == 1


def test_buffer_refresh_evicts_aged_out():
    b = sbuf.CohortBuffer(max_staleness=1)
    b.add(upd(0, round=0, seq=1), now=0.0, current_round=0)
    b.add(upd(1, round=1, seq=1), now=0.1, current_round=1)
    evicted = b.refresh_staleness(2)
    assert [p.update.agent_id for p in evicted] == [0]
    assert len(b) == 1


# ===========================================================================
# staleness weighting
# ===========================================================================

def test_staleness_weight_composes_into_cohort():
    cfg = ssvc.ServeConfig(staleness_alpha=0.5)
    entries = [
        sbuf.Pending(update=upd(0, weight=2.0), arrival_t=0.0, staleness=0),
        sbuf.Pending(update=upd(1, weight=2.0), arrival_t=0.1, staleness=3),
    ]
    _, a = ssvc.assemble_cohort(entries, cfg)
    assert a[0] == pytest.approx(2.0)
    assert a[1] == pytest.approx(2.0 * (1 + 3) ** -0.5)


def test_duplicate_agent_id_in_cohort_is_a_clear_error():
    cfg = ssvc.ServeConfig()
    entries = [
        sbuf.Pending(update=upd(7), arrival_t=0.0, staleness=0),
        sbuf.Pending(update=upd(7, seq=2), arrival_t=0.1, staleness=0),
    ]
    with pytest.raises(ValueError, match="duplicate agent id"):
        ssvc.assemble_cohort(entries, cfg)


# ===========================================================================
# service: admission + participation edge cases
# ===========================================================================

def test_exact_k_min_boundary_commits():
    svc, _ = make_service()
    for agent in range(svc.config.k_min - 1):
        svc.submit(upd(agent, value=0.5))
        assert svc.drain_commits() == []
    svc.submit(upd(svc.config.k_min - 1, value=0.5))
    (c,) = svc.drain_commits()
    assert c.kind == "aggregated" and c.cohort_size == svc.config.k_min
    assert svc.round == 1
    np.testing.assert_allclose(svc.model, 0.5, rtol=1e-5)


def test_zero_participant_round_carries_forward():
    svc, _ = make_service()
    fill_full_cohort(svc, value=0.5)
    w = svc.model
    c = svc.admit_now()
    assert c.kind == "carried_forward" and c.cohort_size == 0
    np.testing.assert_array_equal(svc.model, w)
    assert np.isfinite(svc.model).all()
    assert svc.telemetry.counters["zero_participant_rounds"] == 1
    assert svc.round == 1          # carry does not advance the round


def test_deadline_fires_partial_and_below_quorum_carries():
    svc, clock = make_service()
    # one update (< quorum=2): the deadline must carry, never aggregate
    svc.submit(upd(0, value=3.0))
    assert svc.tick() == []
    clock.advance_to(1.5)
    (c,) = svc.tick()
    assert c.kind == "carried_forward"
    np.testing.assert_array_equal(svc.model, np.zeros(DIM))


def test_all_malicious_partial_cohort_is_trust_clipped():
    svc, clock = make_service(trust_factor=2.0)
    # two honest full cohorts establish the step-norm history
    fill_full_cohort(svc, value=0.5, seq=1)
    fill_full_cohort(svc, value=0.6, seq=2)
    assert svc.round == 2
    w = svc.model
    ema = svc._step_norm_ema
    assert ema is not None and ema > 0
    # deadline cohort of 2, BOTH malicious at +1000
    for agent in range(2):
        svc.submit(upd(agent, round=svc.round, seq=3, value=1000.0))
    clock.advance_to(clock.now() + 2.0)
    (c,) = svc.tick()
    assert c.kind == "degraded_partial" and c.clipped
    step = float(np.linalg.norm(svc.model - w))
    assert np.isfinite(svc.model).all()
    assert step <= 2.0 * ema * (1 + 1e-5)
    assert svc.telemetry.counters["step_clipped"] == 1


def test_carry_mode_never_aggregates_partials():
    svc, clock = make_service(degradation="carry")
    fill_full_cohort(svc, value=0.5, seq=1)
    w = svc.model
    for agent in range(2):
        svc.submit(upd(agent, round=svc.round, seq=2, value=1000.0))
    clock.advance_to(clock.now() + 2.0)
    (c,) = svc.tick()
    assert c.kind == "carried_forward"
    np.testing.assert_array_equal(svc.model, w)


def test_nan_payload_never_reaches_the_estimator():
    svc, _ = make_service()
    bad = np.full(DIM, np.inf, np.float32)
    assert svc.submit(upd(0, payload=bad)) == "rejected_invalid"
    fill_full_cohort(svc, value=0.5, seq=2)
    assert np.isfinite(svc.model).all()


def test_zero_total_weight_refuses_to_average():
    svc, clock = make_service()
    fill_full_cohort(svc, value=0.5, seq=1)
    svc.drain_commits()
    w = svc.model
    for agent in range(svc.config.k_min):
        svc.submit(upd(agent, round=svc.round, seq=2, value=77.0,
                       weight=0.0))
    (c,) = svc.drain_commits()
    assert c.kind == "carried_forward"
    np.testing.assert_array_equal(svc.model, w)
    assert svc.telemetry.counters["zero_weight_rejected"] == 1


# ===========================================================================
# executable cache + fault injection
# ===========================================================================

def test_exec_cache_hits_on_identical_geometry():
    svc, _ = make_service()
    fill_full_cohort(svc, value=0.5, seq=1)
    fill_full_cohort(svc, value=0.6, seq=2)
    fill_full_cohort(svc, value=0.7, seq=3)
    c = svc.telemetry.counters
    assert c["exec_cache_misses"] == 1
    assert c["exec_cache_hits"] == 2
    assert svc.telemetry.post_warmup_misses == 0


def test_launch_fault_recovers_with_retries():
    fails = {"n": 2}

    def hook():
        if fails["n"] > 0:
            fails["n"] -= 1
            raise schaos.FaultInjected("boom")

    clock = SimClock()
    svc = ssvc.AggregationService(
        np.zeros(DIM, np.float32),
        config=ssvc.ServeConfig(k_min=4, backend="jnp"),
        clock=clock, fault_hook=hook)
    fill_full_cohort(svc, value=0.5)
    (c,) = svc.drain_commits()
    assert c.kind == "aggregated" and c.attempts == 3
    assert svc.telemetry.counters["launch_recovered"] == 1
    assert svc.telemetry.counters["launch_retries"] == 2


def test_launch_fault_exhaustion_degrades_but_service_lives():
    def hook():
        raise schaos.FaultInjected("always")

    clock = SimClock()
    svc = ssvc.AggregationService(
        np.zeros(DIM, np.float32),
        config=ssvc.ServeConfig(
            k_min=4, backend="jnp",
            retry=sretry.RetryPolicy(max_attempts=2, base_delay_s=0.01)),
        clock=clock, fault_hook=hook)
    fill_full_cohort(svc, value=0.5)
    (c,) = svc.drain_commits()
    assert c.kind == "carried_forward"
    assert svc.telemetry.counters["launch_failed"] == 1
    np.testing.assert_array_equal(svc.model, np.zeros(DIM))
    # the loop is still alive: a later cohort aggregates normally
    svc._fault_hook = None
    fill_full_cohort(svc, value=0.5, seq=2)
    (c2,) = svc.drain_commits()
    assert c2.kind == "aggregated"


def test_real_launch_error_propagates_instead_of_carrying_forward():
    """Only injected faults are retried: a device or runtime error is
    raised to the caller, never turned into a carried-forward round."""
    calls = {"n": 0}

    def hook():
        calls["n"] += 1
        raise RuntimeError("device lost")

    svc = ssvc.AggregationService(
        np.zeros(DIM, np.float32),
        config=ssvc.ServeConfig(k_min=4, backend="jnp"),
        clock=SimClock(), fault_hook=hook)
    with pytest.raises(RuntimeError, match="device lost"):
        fill_full_cohort(svc, value=0.5)
    assert calls["n"] == 1
    assert svc.telemetry.counters["launch_failed"] == 0
    assert svc.telemetry.counters["carried_forward"] == 0


# ===========================================================================
# chaos config + replay
# ===========================================================================

def test_chaos_rejects_collusion_attacks_per_agent():
    with pytest.raises(ValueError, match="not applicable per-agent"):
        schaos.ChaosConfig(byzantine_frac=0.3, attack="alie")


def test_chaos_fault_modes():
    assert schaos.ChaosConfig().fault_modes() == ()
    assert set(schaos.CHAOS_PROFILES["mixed"].fault_modes()) == {
        "straggler", "dropout", "duplicate", "stale", "byzantine",
        "launch_fault", "partition", "reorder", "corrupt", "slow_loris",
        "crash"}
    assert set(schaos.CHAOS_PROFILES["network"].fault_modes()) == {
        "partition", "reorder", "corrupt", "slow_loris"}


def _replay_spec(rounds, name="serve-test"):
    return ScenarioSpec(name=name, paradigm="federated", num_agents=16,
                        dim=8, num_steps=rounds, step_size=0.05,
                        local_steps=3)


def test_replay_rejects_non_federated_specs():
    spec = ScenarioSpec(paradigm="diffusion", num_agents=5, dim=4,
                        num_steps=2)
    with pytest.raises(ValueError, match="federated"):
        sscenario.replay(spec)


def test_chaos_replay_mixed_profile_stays_in_band():
    rounds = 30
    spec = _replay_spec(rounds)
    res = sscenario.replay(
        spec, chaos=schaos.CHAOS_PROFILES["mixed"],
        serve=ssvc.ServeConfig(k_min=8, deadline_s=1.0, backend="jnp"),
        rounds=rounds, seed=0)
    assert res.rounds_completed == rounds
    assert np.isfinite(res.msd).all()
    # the served model tracks the scenario-runner band for this spec
    assert not res.summary["broke_down"]
    assert res.summary["steady_msd"] <= metrics.breakdown_threshold(spec)
    # every injected fault mode shows recovery activity
    for mode in schaos.CHAOS_PROFILES["mixed"].fault_modes():
        assert res.recoveries[mode] > 0, (mode, res.recoveries)
    # ...and the steady loop never recompiled
    assert res.telemetry["post_warmup_cache_hit"]
    assert res.telemetry["updates_per_sec"] > 0
    for p in (50, 95, 99):
        assert res.telemetry[f"latency_p{p}"] is not None


def test_replay_pallas_backend_smoke():
    rounds = 6
    res = sscenario.replay(
        _replay_spec(rounds, name="serve-pallas"),
        chaos=schaos.ChaosConfig(),
        serve=ssvc.ServeConfig(k_min=8, deadline_s=1.0, backend="pallas",
                               interpret=True),
        rounds=rounds, seed=0)
    assert res.rounds_completed == rounds
    assert np.isfinite(res.msd).all()
    assert res.launch_audit is not None
    assert res.launch_audit["k_pad"] >= 8


# ===========================================================================
# audits: bench rows + the serve-retrace check (mutation fixtures)
# ===========================================================================

def _good_serve_rows():
    base = {
        "scenario": "serve-x", "profile": "clean", "fault_modes": [],
        "recoveries": {}, "rounds_completed": 30,
        "steady_msd": 0.003, "breakdown_level": 0.1, "broke_down": False,
        "latency_p50": 0.2, "latency_p95": 0.5, "latency_p99": 0.6,
        "updates_per_sec": 100.0, "post_warmup_cache_hit": True,
        "post_warmup_misses": 0, "tenants": 1,
        "queue_depth_max": 3, "channel_capacity": 16,
        "duplicate_admissions": 0, "crash_restarts": 0,
    }
    chaosrow = dict(base, profile="mixed", tenants=2, crash_restarts=1,
                    fault_modes=["byzantine", "duplicate", "crash"],
                    recoveries={"byzantine": 5, "duplicate": 3,
                                "crash": 1})
    return [base, chaosrow]


def test_bench_audit_serve_passes_good_rows():
    assert bench_audit.audit_serve({"rows": _good_serve_rows()}) == []


@pytest.mark.parametrize("mutate, needle", [
    (lambda rows: rows[0].update(steady_msd=float("nan")), "non-finite"),
    (lambda rows: rows[0].update(latency_p95=None), "latency_p95"),
    (lambda rows: rows[1].update(broke_down=True), "broke out"),
    (lambda rows: rows[0].update(post_warmup_cache_hit=False), "recompiled"),
    (lambda rows: rows[1]["recoveries"].update(byzantine=0), "no recovery"),
    (lambda rows: rows.pop(1), "no chaos profile"),
    (lambda rows: rows.pop(0), "no clean"),
    (lambda rows: rows[0].update(queue_depth_max=999), "unbounded queue"),
    (lambda rows: rows[0].pop("queue_depth_max"), "queue-depth"),
    (lambda rows: rows[0].pop("channel_capacity"), "capacity bound"),
    (lambda rows: rows[1].update(duplicate_admissions=2),
     "duplicate admission"),
    (lambda rows: rows[1]["fault_modes"].remove("crash"),
     "no crash-restart"),
    (lambda rows: rows[1]["recoveries"].update(crash=0), "crash"),
    (lambda rows: rows[1].update(tenants=1), "multi-tenant"),
])
def test_bench_audit_serve_catches_mutations(mutate, needle):
    rows = _good_serve_rows()
    mutate(rows)
    errors = bench_audit.audit_serve({"rows": rows})
    assert any(needle in e for e in errors), errors


def test_bench_audit_infers_serve_kind(tmp_path):
    p = tmp_path / "BENCH_serve.json"
    assert bench_audit.infer_kind(p) == "serve"


class _FakeTelemetry:
    def __init__(self, commits, misses, hits, post_warmup):
        self.counters = collections.Counter(
            commits=commits, exec_cache_misses=misses, exec_cache_hits=hits)
        self.post_warmup_misses = post_warmup


class _FakeSession:
    def __init__(self, **kw):
        self.telemetry = _FakeTelemetry(**kw)


def test_jaxpr_serve_retrace_catches_recompiles():
    bad = _FakeSession(commits=3, misses=3, hits=0, post_warmup=2)
    findings = jaxpr_audit.check_serve(session=bad)
    assert any(f.rule == "serve-retrace" for f in findings)


def test_jaxpr_serve_retrace_accepts_cached_session():
    good = _FakeSession(commits=3, misses=1, hits=2, post_warmup=0)
    assert [f for f in jaxpr_audit.check_serve(session=good)
            if f.rule == "serve-retrace"] == []
