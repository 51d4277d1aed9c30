"""Training entry point (single-host real runs; the production mesh is
exercised via dryrun.py).

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
      --steps 100 --aggregation rs_mm --malicious 1 --attack additive

Uses the reduced smoke config by default (CPU container); --full-config
loads the assigned full architecture at its published widths, and
--layers cuts its depth to whole layers so it fits one chip.
Simulates the paper's Byzantine agents as data-parallel ranks whose
gradients are corrupted before aggregation.  ``--agents K`` simulates K
aggregation agents on however many devices exist (the sharding
constraints degrade to no-ops; the aggregation statistics are those of
a K-device mesh).

``--scenario`` drives the SAME run through the scenario subsystem
instead of the local loop: the CLI arguments are lowered to a
``ScenarioSpec(paradigm="substrate", ...)`` and executed by
``scenarios.run`` -- one declarative spec, the shared scan loop, uniform
loss/consensus histories, the spec-derived attack summary, and the
per-layout kernel launch audit (``--use-kernel``), with compile and
steady wall clock reported separately.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat, configs
from repro.checkpoint import checkpoint as ckpt
from repro.core import attacks
from repro.data import synthetic
from repro.launch import steps
from repro.launch.mesh import make_host_mesh, num_agents
from repro.models import model as M
from repro.optim import optimizers


def build(args):
    mesh = make_host_mesh(model=args.model_parallel)
    if args.full_config:
        model = configs.load_arch(args.arch).model
    else:
        model = configs.load_smoke(args.arch)
    if args.layers:
        if args.full_config and args.layers < model.num_layers:
            print(f"# depth cut: {args.layers} of {model.num_layers} "
                  "layers (whole layers; widths as published)")
        model = dataclasses.replace(model, num_layers=args.layers)
    if args.d_model:
        # keep head structure consistent when scaling width
        scale = args.d_model // model.d_model
        model = dataclasses.replace(
            model, d_model=args.d_model, d_ff=model.d_ff * max(scale, 1))
    par = configs.ParallelConfig(
        fsdp=False, microbatches=args.microbatches,
        aggregation=args.aggregation, use_kernel=args.use_kernel)
    opt_cfg = optimizers.OptimizerConfig(
        learning_rate=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
        total_steps=args.steps)
    byz = None
    if args.malicious:
        byz = attacks.ByzantineConfig(
            num_malicious=args.malicious, attack=args.attack,
            attack_kwargs=_attack_kwargs(args))
    step, _ = steps.make_train_step_gspmd(model, par, opt_cfg, mesh, byz,
                                          k_agents=args.agents or None)
    return mesh, model, par, opt_cfg, jax.jit(step, donate_argnums=(0, 1))


def _attack_kwargs(args) -> tuple:
    # --delta only parameterizes the additive attack; every other
    # registry attack has its own kwargs (or none) and would reject it
    return (("delta", args.delta),) if args.attack == "additive" else ()


def run_scenario(args) -> list:
    """Lower the CLI run to a substrate ScenarioSpec and execute it
    through scenarios.run (the shared scan loop)."""
    from repro import scenarios  # deferred: keep the direct path light

    if args.full_config:
        raise SystemExit(
            "--scenario runs the reduced smoke config (the substrate "
            "adapter builds configs.load_smoke); drop --full-config")
    k = args.agents or num_agents(make_host_mesh(model=args.model_parallel))
    per_agent = max(1, args.batch // k)
    spec = scenarios.ScenarioSpec(
        paradigm="substrate", model_config=args.arch,
        aggregator="mean" if args.aggregation == "mean" else "mm_tukey",
        backend="pallas" if args.use_kernel else "jnp",
        attack=args.attack, num_malicious=args.malicious,
        attack_kwargs=_attack_kwargs(args) if args.malicious else (),
        num_agents=k, num_steps=args.steps, step_size=args.lr,
        paradigm_kwargs=(
            ("batch_per_agent", per_agent), ("seq_len", args.seq),
            ("microbatches", args.microbatches),
            ("aggregation", args.aggregation
             if args.aggregation != "mean" else "rs_mm"),
            ("num_layers", args.layers), ("d_model", args.d_model),
            ("model_parallel", args.model_parallel),
        ))
    print(f"# scenario {spec.label()}")
    res = scenarios.run(spec)
    losses = [float(x) for x in res.history["loss"]]
    for i in range(0, args.steps, max(1, args.log_every)):
        print(f"step {i:5d} loss {losses[i]:.4f} "
              f"consensus {float(res.history['consensus'][i]):.3f}")
    print(f"# compile {res.compile_s:.2f}s  steady wall "
          f"{res.wall_clock_s:.2f}s  broke_down={res.summary['broke_down']}")
    if res.launch_audit:
        n = res.launch_audit.get("n_layouts", 1)
        print(f"# launch audit: {n} aggregated tree layout(s)")
    print(f"# first-10 mean loss {np.mean(losses[:10]):.4f} -> "
          f"last-10 mean {np.mean(losses[-10:]):.4f}")
    return losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--aggregation", default="rs_mm",
                    choices=["mean", "gather_mm", "rs_mm"])
    ap.add_argument("--use-kernel", action="store_true",
                    help="fused Pallas MM kernel inside the aggregation")
    ap.add_argument("--agents", type=int, default=0,
                    help="simulate K aggregation agents (default: the "
                         "mesh's device-derived agent count)")
    ap.add_argument("--malicious", type=int, default=0)
    ap.add_argument("--attack", default="additive")
    ap.add_argument("--delta", type=float, default=1000.0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--scenario", action="store_true",
                    help="run through scenarios.run as a substrate "
                         "ScenarioSpec instead of the local loop")
    args = ap.parse_args(argv)

    if args.scenario:
        return run_scenario(args)

    mesh, model, par, opt_cfg, step = build(args)
    k = args.agents or num_agents(mesh)
    batch = args.batch
    if batch % k:
        batch = k * max(1, batch // k)
        print(f"# rounding batch to {batch} (divisible by {k} agents)")

    params = M.init_model(jax.random.key(0), model)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    opt = optimizers.init(opt_cfg, params)
    stream = synthetic.token_batches(synthetic.TokenStreamConfig(
        vocab_size=model.vocab_size, seq_len=args.seq, batch_size=batch))

    def device_batch(i):
        jb = {"tokens": jnp.asarray(next(stream)["tokens"])}
        if model.arch_type == "vlm":
            jb["prefix"] = jnp.zeros(
                (batch, model.num_prefix_tokens, model.d_model),
                jnp.dtype(model.act_dtype))
        if model.arch_type == "audio":
            jb["frames"] = 0.02 * jax.random.normal(
                jax.random.fold_in(jax.random.key(1), i),
                (batch, model.num_prefix_tokens, model.d_model),
                jnp.dtype(model.act_dtype))
        return jb

    dev = mesh.devices.flat[0]
    print(f"# arch={model.name} params={n_params/1e6:.1f}M agents={k} "
          f"agg={par.aggregation} kernel={par.use_kernel} "
          f"malicious={args.malicious} batch={batch}x{args.seq} "
          f"mesh={dict(mesh.shape)} on {mesh.devices.size} "
          f"{dev.platform} device(s)")
    jb = device_batch(0)
    t0 = time.perf_counter()
    with compat.compile_clock() as clock:
        step = step.lower(params, opt, jb).compile()
    print(f"# step compile {time.perf_counter() - t0:.2f}s (trace "
          f"{clock['trace_s']:.2f}s, lower {clock['lower_s']:.2f}s, "
          f"compile or cache load {clock['compile_s']:.2f}s, "
          f"{clock['cache_hits']} cache hit(s))", flush=True)
    losses, step_s = [], []
    for i in range(args.steps):
        if i:
            jb = device_batch(i)
        t0 = time.perf_counter()
        params, opt, metrics = step(params, opt, jb)
        losses.append(float(metrics["loss"]))     # waits for the step
        step_s.append(time.perf_counter() - t0)
        if i % args.log_every == 0 or i == args.steps - 1:
            print(f"step {i:5d} loss {losses[-1]:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"{step_s[-1]*1e3:.1f} ms", flush=True)
    if len(step_s) > 1:
        print(f"# steady step {np.median(step_s[1:])*1e3:.1f} ms "
              f"(median of steps 1..{len(step_s) - 1})")
    if args.checkpoint:
        ckpt.save(args.checkpoint, params, step=args.steps)
        print(f"# saved {args.checkpoint}")
    print(f"# first-10 mean loss {np.mean(losses[:10]):.4f} -> "
          f"last-10 mean {np.mean(losses[-10:]):.4f}")
    return losses


if __name__ == "__main__":
    compat.enable_persistent_compilation_cache()
    main()
