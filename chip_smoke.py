"""Smoke run of the main path on TPU: the robust-aggregation kernel, the
robust training step and the streaming service, through the entry points
a user calls.

  python chip_smoke.py               # one chip: device, kernel, train, serve
  python chip_smoke.py --four-chips  # four chips: the data=4 train step only

Phases run in one process, in order; the first that fails exits non-zero.

  device  ``jax.devices()[0]`` must be a TPU.  There is no CPU fallback.
  kernel  both Pallas kernel paths through ``ops.AggregationEngine`` at
          real widths, each compared with ``kernels/ref.py`` and checked
          to have lowered through Mosaic (``tpu_custom_call``).
  train   ``repro.launch.train.main`` on qwen3-0.6b at its published
          widths with its depth cut to ``TRAIN_LAYERS`` whole layers:
          K=4 agents (one byzantine), rs_mm with the kernel, then the same
          step with mean aggregation.  The robust loss must stay finite.
  serve   ``repro.serve.scenario.replay`` (what examples/serve_agg.py
          calls): clean profile, 16 agents, k_min=8, dim 2**22 (one
          qwen3-0.6b MLP matrix), pallas backend.  Every round must
          commit as ``aggregated`` with no failed launch or lost update.

Each phase prints what it ran, its shapes, compile and run seconds and
the device's ``peak_bytes_in_use``.  On success the last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
# libtpu otherwise writes its logs to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compat  # noqa: E402
from repro.core import graph  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402

# Deepest whole-layer cut of qwen3-0.6b (28 layers) whose K=4 rs_mm
# kernel step fits one 16 GB v5e chip: compiled for v5e, 16 layers take
# 6.29 GiB of arguments and 6.41 GiB of temporaries; 20 take 15.71 GiB.
TRAIN_LAYERS = 16
TRAIN_STEPS = 5

# Kernel and oracle are different f32 programs (Mosaic vs XLA), so they
# agree to rounding: ATOL near zero, 8 f32 ulps (2**-20 relative) at the
# outliers' magnitude, where one ulp is already 7.6e-6.
ATOL, RTOL = 1e-5, 2.0 ** -20
KERNEL_CASES = (
    # (label, K rows, M coords, N weight columns or None, expected path)
    ("single-pass unweighted", 8, 2 ** 22, None, "single"),
    ("single-pass weighted batched (diffusion ring, N=K)", 16, 2 ** 20, 16,
     "single"),
    ("two-pass, K=1024 clients at participation 0.5", 512, 2 ** 20, None,
     "two_pass"),
)


class PhaseFailed(RuntimeError):
    pass


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def peak_bytes() -> str:
    """Largest ``peak_bytes_in_use`` over the local devices."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return f"{max(peaks)} B" if peaks else "not reported"


def _median_s(fn, *args, reps: int = 3) -> float:
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def phase_device(count: int) -> jax.Device:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise PhaseFailed(
            f"no TPU found: JAX sees {len(devs)} {devs[0].platform} "
            "device(s); this smoke run never falls back to the CPU")
    if len(devs) < count:
        raise PhaseFailed(f"need {count} TPU chips, JAX sees {len(devs)}")
    log("device", f"{len(devs)} x {devs[0].device_kind} "
                  f"({devs[0].platform})")
    return devs[0]


def phase_kernel(cases=KERNEL_CASES) -> None:
    """Each case through the engine vs the jnp oracle, to ``ATOL`` +
    ``RTOL`` x |oracle| (both paths are exact at these shapes).  On a TPU
    the compiled program must also hold a Mosaic kernel; elsewhere the
    Pallas interpreter runs."""
    mosaic = jax.devices()[0].platform == "tpu"
    eng = ops.AggregationEngine()
    for i, (label, k, m, n, want_path) in enumerate(cases):
        x = jax.random.normal(jax.random.key(i), (k, m), jnp.float32)
        x = x.at[-(k // 4):].add(100.0)       # a quarter are outliers
        if n is None:
            fn, args = jax.jit(eng.aggregate), (x,)
            oracle = jax.jit(ref.mm_aggregate_ref)
        else:
            a = jnp.asarray(graph.metropolis_weights(
                graph.ring(k, hops=2)), jnp.float32)[:, :n]
            fn, args = jax.jit(eng.aggregate_batched), (x, a)
            oracle = jax.jit(ref.mm_aggregate_batched_ref)
        with ops.record_workloads() as rec:
            t0 = time.perf_counter()
            compiled = fn.lower(*args).compile()
            compile_s = time.perf_counter() - t0
        path = rec[0]["path"]
        shape = f"x={tuple(x.shape)}" + ("" if n is None else f" a=({k}, {n})")
        log("kernel", f"{label}: {shape} path={path} "
                      f"block_m={rec[0]['block_m']} "
                      f"block_k={rec[0]['block_k']}")
        if path != want_path:
            raise PhaseFailed(f"{label}: engine chose {path}, "
                              f"expected {want_path}")
        if mosaic and "tpu_custom_call" not in compiled.as_text():
            raise PhaseFailed(f"{label}: no tpu_custom_call in the compiled "
                              "program (the kernel did not lower via Mosaic)")
        got = jax.block_until_ready(compiled(*args))
        run_s = _median_s(compiled, *args)
        want = oracle(*args)
        diff = jnp.abs(got - want)
        err = float(jnp.max(diff))
        excess = float(jnp.max(diff - RTOL * jnp.abs(want)))
        log("kernel", f"{label}: compile {compile_s:.2f}s run "
                      f"{run_s * 1e3:.3f} ms max|kernel-ref| {err:.3g} "
                      f"(max|ref| {float(jnp.max(jnp.abs(want))):.4g}) "
                      f"peak {peak_bytes()}")
        if not bool(jnp.all(jnp.isfinite(got))) or not excess <= ATOL:
            raise PhaseFailed(f"{label}: kernel differs from ref by {err} "
                              f"(atol {ATOL}, rtol {RTOL})")
        del x, args, got, want, diff


def phase_train(*, layers: int = TRAIN_LAYERS, steps: int = TRAIN_STEPS,
                full_config: bool = True, seq: int = 256) -> None:
    """The robust step (rs_mm + kernel) and the same step with mean."""
    from repro.launch import train

    base = ["--arch", "qwen3-0.6b", "--layers", str(layers),
            "--agents", "4", "--malicious", "1", "--batch", "8",
            "--seq", str(seq), "--steps", str(steps), "--log-every", "1"]
    if full_config:
        base.append("--full-config")
    for agg, extra in (("rs_mm", ["--use-kernel"]), ("mean", [])):
        log("train", f"aggregation={agg} {' '.join(extra)}")
        losses = train.main(base + ["--aggregation", agg] + extra)
        log("train", f"aggregation={agg} losses "
                     f"{[round(v, 4) for v in losses]} peak {peak_bytes()}")
        if agg == "rs_mm" and not all(math.isfinite(v) for v in losses):
            raise PhaseFailed(f"robust step lost finiteness: {losses}")


def phase_serve(*, dim: int = 2 ** 22, rounds: int = 4) -> None:
    from repro.scenarios.spec import ScenarioSpec
    from repro.serve import CHAOS_PROFILES, ServeConfig, replay

    agents, k_min = 16, 8

    spec = ScenarioSpec(name="chip-smoke-serve", paradigm="federated",
                        num_agents=agents, dim=dim, num_steps=rounds,
                        step_size=0.05, local_steps=3)
    log("serve", f"replay clean profile: {agents} agents, k_min={k_min}, "
                 f"dim={dim}, {rounds} rounds, pallas backend")
    res = replay(spec, chaos=CHAOS_PROFILES["clean"],
                 serve=ServeConfig(k_min=k_min, backend="pallas"),
                 rounds=rounds, seed=0)
    counters = res.telemetry["counters"]
    compile_s = sum(c.compile_s for c in res.commits)
    launch_ms = [round(c.launch_wall_s * 1e3, 3) for c in res.commits]
    log("serve", f"{res.rounds_completed}/{rounds} rounds in "
                 f"{res.wall_s:.2f}s wall ({res.wall_s / max(rounds, 1):.2f}"
                 f" s/round); kinds {[c.kind for c in res.commits]}; "
                 f"compile {compile_s:.2f}s; launch ms {launch_ms}; "
                 f"peak {peak_bytes()}")
    bad = {k: counters.get(k, 0)
           for k in ("launch_failed", "updates_lost", "carried_forward")}
    log("serve", f"counters {bad}, steady MSD "
                 f"{res.summary['steady_msd']:.4g}")
    if res.rounds_completed < rounds:
        raise PhaseFailed(f"only {res.rounds_completed}/{rounds} rounds")
    if any(c.kind != "aggregated" for c in res.commits) or any(bad.values()):
        raise PhaseFailed(f"a round did not aggregate: {bad}")
    if not np.all(np.isfinite(res.model)):
        raise PhaseFailed("served model is not finite")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the train phase on a data=4 mesh "
                         "over four chips (robust vs mean)")
    ns = ap.parse_args(argv)
    count = 4 if ns.four_chips else 1
    try:
        dev = phase_device(count)
        compat.enable_persistent_compilation_cache()
        if ns.four_chips:
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh()
            ids = sorted({d.id for d in mesh.devices.flat})
            log("device", f"host mesh {dict(mesh.shape)} spans device ids "
                          f"{ids}")
            if len(ids) != 4 or mesh.shape["data"] != 4:
                raise PhaseFailed(f"mesh does not span 4 chips: {ids}")
            phase_train()
        else:
            phase_kernel()
            phase_train()
            phase_serve()
    except PhaseFailed as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
