"""Driver of the serving cells: the program's streaming aggregation
service behind its transport front (``repro.serve.TransportFront``,
``offer`` and ``pump`` on the wall clock), one tenant.

Traffic (``bench/traffic/<name>.json``) is open loop: a fixed number of
updates per second, gaps that are the quantiles of an exponential
shuffled by the seed, clients drawn by Zipf activity with the attackers'
share exact.  Payloads come from a pool made on the device from the seed
in set-up (honest rows: a fixed optimum plus Gaussian noise; attack
rows: an honest row shifted on every coordinate), so offering an update
costs O(1).  Each honest update is timed from its scheduled send time to
the end of the ``pump`` call in which the commit that took it ran.
After the window the stream goes on until every honest update due in
the window is committed or superseded (at most ``drain_s``).  Then the
plain reference (``bench/reference/fedbuff.py``) replays every commit
from the cohorts the server formed and the models are compared.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Dict, List, Tuple

import jax
import numpy as np

from bench import weights, work
from bench.reference import fedbuff

TENANT = "cell"


@dataclasses.dataclass
class Schedule:
    t: np.ndarray            # scheduled send times, s from the window start
    client: np.ndarray
    attack: np.ndarray       # bool
    pool: np.ndarray         # pool row
    stale: np.ndarray        # rounds behind at send
    n_window: int            # the first n_window are due in the window


def schedule(traffic: dict, seed: int, seconds: float, drain_s: float
             ) -> Tuple[Schedule, np.ndarray]:
    """The arrivals of one run and the clients' weights.  Every seed gets
    the same counts and gaps, in another order."""
    rng = np.random.default_rng([int(seed), 0x5E12E])
    rate = float(traffic["rate_per_s"])
    n_clients = int(traffic["clients"])
    ranks = np.arange(1, n_clients + 1, dtype=np.float64)
    act = ranks ** -float(traffic["zipf_s"])
    attackers = np.asarray(traffic["attacker_ranks"]) - 1
    is_att = np.zeros(n_clients, bool)
    is_att[attackers] = True
    share = float(traffic["attacker_share"])

    def part(n: int, span: float):
        q = -np.log1p(-(np.arange(n) + 0.5) / n)
        gaps = rng.permutation(q)
        t = (np.cumsum(gaps) - gaps[0]) / np.sum(gaps) * span
        n_att = int(round(share * n))
        kind = rng.permutation(np.arange(n) < n_att)
        honest_p = np.where(is_att, 0.0, act)
        att_p = np.where(is_att, act, 0.0)
        cl = np.where(kind,
                      rng.choice(n_clients, n, p=att_p / att_p.sum()),
                      rng.choice(n_clients, n, p=honest_p / honest_p.sum()))
        n_h, n_a = int(traffic["pool_honest"]), int(traffic["pool_attack"])
        pool = np.where(kind, n_h + rng.integers(0, n_a, n),
                        rng.integers(0, n_h, n))
        stale = np.where(kind, 0,
                         rng.integers(0, int(traffic["staleness_max"]) + 1, n))
        return t, cl, kind, pool, stale

    n_w = int(round(rate * seconds))
    n_d = int(math.ceil(rate * drain_s))
    a = part(n_w, seconds * (1.0 - 0.5 / n_w))
    b = part(n_d, drain_s)
    b = (b[0] + seconds,) + b[1:]
    sched = Schedule(*(np.concatenate([x, y]) for x, y in zip(a, b)),
                     n_window=n_w)
    w = rng.uniform(0.5, 1.5, n_clients)
    return sched, w


def build(cfg: dict, seed: int, traffic: dict):
    """The payload pool on the device and on the host, and the model."""
    key = jax.random.fold_in(weights.base_key(seed), 0xF00D)
    pool_dev, _ = fedbuff.make_pool_jit(
        key, dim=int(cfg["dim"]), n_honest=int(traffic["pool_honest"]),
        n_attack=int(traffic["pool_attack"]),
        scale=float(traffic["optimum_scale"]), sd=float(traffic["honest_sd"]),
        shift_sd=float(traffic["attack_shift_sd"]))
    pool_host = np.asarray(pool_dev)
    model0 = np.zeros(int(cfg["dim"]), np.float32)
    return pool_dev, pool_host, model0


def serve_config(cfg: dict):
    from repro.serve import ServeConfig
    return ServeConfig(**cfg["serve"])


def warm_up(cfg: dict, pool_host: np.ndarray, exec_cache, log) -> None:
    """Compile the launch programs this traffic uses (the full cohort
    and the deadline-admitted partial one) on a tenant of its own that
    shares the executable cache, so the measured tenant starts fresh."""
    from repro.kernels import ops
    from repro.serve import AgentUpdate, TransportFront
    from repro.serve.transport import TransportConfig
    sc = serve_config(cfg)
    front = TransportFront(config=TransportConfig(**cfg["transport"]),
                           exec_cache=exec_cache)
    svc = front.add_tenant("warm", np.zeros(pool_host.shape[1], np.float32),
                           config=sc)
    with ops.record_workloads() as rec:
        for j in range(sc.k_min + 3):
            front.offer("warm", AgentUpdate(
                agent_id=10 ** 6 + j, round=svc.round,
                payload=pool_host[j % pool_host.shape[0]], seq=1))
            front.pump()
        svc.admit_now()
    kinds = [c.kind for c in svc.drain_commits()]
    log(f"# warm-up commits {kinds}; launch workloads "
        + "; ".join(f"k={r['k']} m={r['m']} path={r['path']} "
                    f"block_m={r['block_m']} block_k={r['block_k']}"
                    for r in rec))


@dataclasses.dataclass
class Stream:
    """What the harness saw of one run."""
    sent: Dict[Tuple[int, int], int]                # (client, seq) -> index
    round_tag: Dict[Tuple[int, int], int]
    done_at: Dict[int, float]                       # index -> commit end
    superseded: set
    rejected: Dict[int, str]
    commits: list                                   # CommitResult in order
    commit_end: List[float]
    models: Dict[int, np.ndarray]                   # commit index -> model
    lateness: List[float]
    verdicts: collections.Counter
    host_s: float                                   # offer + pump, window
    window_commits: int
    end_s: float


def stream(front, svc, sched: Schedule, client_w, pool_host, seconds: float,
           drain_s: float, sample: set) -> Stream:
    """Offer the schedule open loop; pump; record commits."""
    from repro.serve import AgentUpdate
    seq = collections.Counter()
    sent, tag = {}, {}
    pending: Dict[int, int] = {}                    # client -> index
    done_at, superseded, rejected = {}, set(), {}
    commits, commit_end, models = [], [], {}
    lateness, verdicts = [], collections.Counter()
    host_s, window_commits = 0.0, 0
    n = len(sched.t)
    due_honest = {i for i in range(sched.n_window) if not sched.attack[i]}
    unresolved = set(due_honest)
    i = 0
    t0 = time.perf_counter()
    window = jax.profiler.TraceAnnotation("bench.window")
    window.__enter__()
    in_window = True
    while True:
        now = time.perf_counter() - t0
        if in_window and now >= seconds:
            window.__exit__(None, None, None)
            in_window = False
        if not in_window and (not unresolved or now >= seconds + drain_s):
            break
        h0 = time.perf_counter()
        if i < n and sched.t[i] <= now:
            with jax.profiler.TraceAnnotation("bench.offer"):
                while i < n and sched.t[i] <= now:
                    c = int(sched.client[i])
                    seq[c] += 1
                    u = AgentUpdate(
                        agent_id=c, round=max(svc.round - int(sched.stale[i]), 0),
                        payload=pool_host[sched.pool[i]],
                        weight=float(client_w[c]), seq=seq[c],
                        sent_at=float(sched.t[i]))
                    sent[(c, seq[c])] = i
                    tag[(c, seq[c])] = u.round
                    lateness.append(now - float(sched.t[i]))
                    v = front.offer(TENANT, u)
                    if v != "enqueued":
                        verdicts["offer_" + v] += 1
                        rejected[i] = v
                        unresolved.discard(i)
                    i += 1
        with jax.profiler.TraceAnnotation("bench.pump"):
            receipts = front.pump()
        end = time.perf_counter() - t0
        if in_window:
            host_s += time.perf_counter() - h0
        for r in receipts:
            verdicts[r.verdict] += 1
            j = sent[(r.agent_id, r.seq)]
            if r.verdict in ("buffered", "superseded"):
                old = pending.get(r.agent_id)
                if r.verdict == "superseded" and old is not None:
                    superseded.add(old)
                    unresolved.discard(old)
                pending[r.agent_id] = j
            else:
                rejected[j] = r.verdict
                unresolved.discard(j)
        for cm in svc.drain_commits():
            commits.append(cm)
            commit_end.append(end)
            if end <= seconds:
                window_commits += 1
            if len(commits) - 1 in sample:
                models[len(commits) - 1] = svc.model
            for a, s in cm.seqs:
                j = sent[(a, s)]
                done_at[j] = end
                unresolved.discard(j)
                if pending.get(a) == j:
                    del pending[a]
        if not receipts and (i >= n or sched.t[i] > end):
            nxt = float(sched.t[i]) if i < n else end + 1e-3
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(min(max(nxt - end, 0.0), 2e-3))
    if in_window:
        window.__exit__(None, None, None)
    if commits and len(commits) - 1 not in models:
        models[len(commits) - 1] = svc.model
    return Stream(sent=sent, round_tag=tag, done_at=done_at,
                  superseded=superseded, rejected=rejected, commits=commits,
                  commit_end=commit_end, models=models, lateness=lateness,
                  verdicts=verdicts, host_s=host_s,
                  window_commits=window_commits,
                  end_s=time.perf_counter() - t0)


def replay(cfg, traffic, st: Stream, sched: Schedule, client_w, pool_dev,
           model0, dtype="float32"):
    """The reference's commits for the program's cohorts: {commit index:
    model} at the sampled commits, and the commits whose kind, outliers
    or clip differ from the program's."""
    policy = dict(cfg["serve"])
    srv = fedbuff.Server(policy, model0, pool_dev, dtype=dtype)
    models, mismatched = {}, []
    for j, cm in enumerate(st.commits):
        if cm.kind == "carried_forward":
            continue
        members = []
        for a, s in cm.seqs:
            idx = st.sent[(a, s)]
            members.append(fedbuff.Member(
                client=a, pool_index=int(sched.pool[idx]),
                weight=float(client_w[a]), round_tag=st.round_tag[(a, s)]))
        outliers, clipped = srv.commit(members)
        if set(outliers) != set(cm.outliers) or clipped != cm.clipped \
                or (cm.kind == "degraded_partial") != (len(members) < int(policy["k_min"])):
            mismatched.append(j)
        if j in st.models:
            models[j] = srv.model()
    return models, mismatched


def model_gap(prog: Dict[int, np.ndarray], ref: Dict[int, np.ndarray],
              sd: float) -> float:
    """The widest |model - reference| over the compared commits, in
    honest standard deviations."""
    gaps = [float(np.max(np.abs(prog[j] - ref[j]))) / sd for j in ref]
    return max(gaps) if gaps else float("nan")


def latencies(st: Stream, sched: Schedule) -> Tuple[np.ndarray, int, int]:
    """Latency (s) of every honest update due in the window that was not
    superseded; one never committed counts the time until the run gave
    up on it, which sorts after every committed one."""
    lat, failed, attempted = [], 0, 0
    for i in range(sched.n_window):
        if sched.attack[i]:
            continue
        attempted += 1
        if i in st.superseded:
            continue
        if i in st.done_at:
            lat.append(st.done_at[i] - float(sched.t[i]))
        else:
            failed += 1
            lat.append(max(st.end_s - float(sched.t[i]), st.end_s))
    return np.asarray(lat), attempted, failed


def sample_commits(seed: int, n: int = 4096, share: float = 0.125) -> set:
    rng = np.random.default_rng([int(seed), 0xC0441])
    return set(np.nonzero(rng.random(n) < share)[0].tolist())


def one_run(cfg, traffic, seed, seconds, *, log=print, trace=None):
    """Set up, stream and replay one run; returns everything measured."""
    from repro.serve import TransportFront
    from repro.serve.service import ExecutableCache
    from repro.serve.transport import TransportConfig
    drain_s = float(traffic["drain_s"])
    sched, client_w = schedule(traffic, seed, seconds, drain_s)
    pool_dev, pool_host, model0 = build(cfg, seed, traffic)
    cache = ExecutableCache()
    warm_up(cfg, pool_host, cache, log)
    front = TransportFront(config=TransportConfig(**cfg["transport"]),
                           exec_cache=cache)
    svc = front.add_tenant(TENANT, model0, config=serve_config(cfg))
    t_setup = time.perf_counter()
    if trace is not None:
        trace.start_trace()
    st = stream(front, svc, sched, client_w, pool_host, seconds, drain_s,
                sample_commits(seed))
    if trace is not None:
        trace.stop_trace()
    return sched, client_w, (pool_dev, pool_host, model0), svc, st, t_setup


def run(ctx):
    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    sched, client_w, pool, svc, st, t_setup = one_run(
        cfg, traffic, ctx.seed, ctx.seconds, log=ctx.log, trace=ctx)
    setup_s = t_setup - ctx.t_process
    from bench.run import memory_peak_bytes
    peak = memory_peak_bytes(ctx.devices)
    lat, attempted, failed = latencies(st, sched)
    seconds = ctx.seconds
    committed_in_window = sum(1 for i, t in st.done_at.items()
                              if t <= seconds and not sched.attack[i])
    att_idx = [i for i in range(sched.n_window) if sched.attack[i]]
    att_rej = collections.Counter(st.rejected.get(i, "") for i in att_idx)
    outl = sum(1 for cm in st.commits for a in cm.outliers)
    late = np.asarray(st.lateness[:sched.n_window])
    ctx.log(f"# offered {sched.n_window} in the window "
            f"({len(att_idx)} attacker), {len(st.commits)} commits, "
            f"{st.window_commits} in the window; kinds "
            f"{dict(collections.Counter(c.kind for c in st.commits))}")
    ctx.log(f"# honest: attempted {attempted} superseded "
            f"{len(st.superseded & set(range(sched.n_window)))} failed {failed}; "
            f"attacker offers {len(att_idx)} quarantined "
            f"{att_rej.get('rejected_quarantined', 0)} estimator outliers {outl}")
    ctx.log(f"# generator lateness ms: p50 {1e3 * np.median(late):.3f} "
            f"p95 {1e3 * np.percentile(late, 95):.3f} max {1e3 * late.max():.3f}")
    ctx.log(f"# verdicts {dict(st.verdicts)}")
    pool_dev, _, model0 = pool
    del svc
    ref_models, mismatched = replay(cfg, traffic, st, sched, client_w,
                                    pool_dev, model0)
    gap = model_gap(st.models, ref_models, float(traffic["honest_sd"]))
    ctx.log(f"# compared {len(ref_models)} commits; mismatched {mismatched[:10]}")
    limits = traffic["limits"]
    in_win = [c for c, e in zip(st.commits, st.commit_end) if e <= seconds]
    launch = [c.launch_wall_s for c in in_win if c.kind != "carried_forward"]
    facts = {
        "commits": len(launch), "launch_wall_s": launch,
        "host_s": st.host_s, "window_s": seconds,
        "chips": len(ctx.devices), "device_kind": ctx.devices[0].device_kind,
        "launch_bytes": work.mm_bytes(int(cfg["serve"]["k_min"]),
                                      int(cfg["dim"]), weighted=True),
    }
    from bench.run import DriverResult
    p95 = float(np.percentile(lat, 95)) * 1e3 if lat.size else None
    return DriverResult(
        metrics={"setup_s": setup_s, "update_p95_ms": p95,
                 "updates_per_s": committed_in_window / seconds},
        checks={"model_gap_sd": (gap, limits["model_gap_sd"]),
                "commits_mismatched": (len(mismatched),
                                       limits["commits_mismatched"])},
        attempted=attempted, failed=failed, memory_peak_bytes=peak,
        facts=facts,
        correct=bool(ref_models) and failed == 0 and math.isfinite(gap))


def calibrate(cell, devices, seeds, control_seeds, log):
    """The program against the reference on ``seeds`` at the cell's own
    load and window; on ``control_seeds`` the reference in bfloat16 in
    the program's place."""
    cfg, traffic = cell.config, cell.traffic
    seconds = float(traffic.get("calibrate_seconds", 10))
    out = []
    for seed in seeds:
        sched, client_w, pool, svc, st, _ = one_run(
            cfg, traffic, seed, seconds, log=log)
        del svc
        ref, mism = replay(cfg, traffic, st, sched, client_w, pool[0], pool[2])
        sd = float(traffic["honest_sd"])
        row = {"seed": seed, "kind": "program", "commits": len(st.commits),
               "model_gap_sd": model_gap(st.models, ref, sd),
               "commits_mismatched": len(mism)}
        log(row)
        out.append(row)
        if seed in control_seeds:
            ctl, mism_c = replay(cfg, traffic, st, sched, client_w, pool[0],
                                 pool[2], dtype="bfloat16")
            row = {"seed": seed, "kind": "control_bf16",
                   "model_gap_sd": model_gap(ctl, ref, sd),
                   "commits_mismatched": len(mism_c)}
            log(row)
            out.append(row)
        del pool, st
    return out


def sweep(cell, devices, rates, seconds, log):
    """The knee: latency and backlog at each offered rate."""
    cfg = cell.config
    out = []
    for rate in rates:
        traffic = dict(cell.traffic, rate_per_s=float(rate), drain_s=5.0)
        sched, client_w, pool, svc, st, _ = one_run(
            cfg, traffic, 1000 + int(rate), seconds, log=log)
        lat, attempted, failed = latencies(st, sched)
        due_late = sum(1 for i in range(sched.n_window)
                       if not sched.attack[i] and i not in st.superseded
                       and st.done_at.get(i, 1e9) > seconds)
        q = len(lat) // 4
        row = {"rate": rate, "p50_ms": float(np.median(lat)) * 1e3,
               "p95_ms": float(np.percentile(lat, 95)) * 1e3,
               "first_quarter_p50_ms": float(np.median(lat[:q])) * 1e3,
               "last_quarter_p50_ms": float(np.median(lat[-q:])) * 1e3,
               "backlog_at_close": due_late, "failed": failed,
               "commits": len(st.commits),
               "committed_per_s": sum(1 for i, t in st.done_at.items()
                                      if t <= seconds and not sched.attack[i])
               / seconds}
        log(row)
        out.append(row)
        del pool, st, svc
    return out
