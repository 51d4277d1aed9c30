"""Driver of the training cells: the program's data-parallel robust
train step (``repro.launch.steps.make_train_step_gspmd``, what
``repro.launch.train`` builds), jitted with its state donated.

Set-up builds the step once, makes the weights and the token rows on
the device from the seed, compiles, and drives that same compiled step
through its first ``check_steps`` steps (capturing the per-leaf norm of
the first gradient from Adam's first moment, and the per-leaf norm of
the parameters' change after the last of them).  The window then goes
on with the same step and feed: at most two steps in flight, ending at
``block_until_ready`` of the last step dispatched before ``--seconds``
passed.  After the window the program's state is freed and the plain
reference (``bench/reference/qwen3.py``) runs the same first steps.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights, work
from bench.reference import qwen3 as reference


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qk_norm=True, qkv_bias=bool(cfg["attention_bias"]),
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        act_dtype=cfg["torch_dtype"], q_chunk=cfg["seq_len"])


def build_step(cfg: dict, job: dict, devices):
    """The jitted step, its mesh and the parameter template."""
    from repro.configs.base import ParallelConfig
    from repro.core import attacks
    from repro.launch import steps
    from repro.models import model as M
    from repro.optim import optimizers

    model = model_config(cfg)
    mesh = jax.make_mesh((len(devices), 1), ("data", "model"),
                         devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    par = ParallelConfig(fsdp=False, microbatches=1,
                         aggregation=job["aggregation"],
                         use_kernel=bool(job["use_kernel"]),
                         agg_num_iters=int(job["agg_iters"]))
    opt_cfg = optimizers.OptimizerConfig(**job["optimizer"])
    byz = None
    if job["malicious"]:
        byz = attacks.ByzantineConfig(
            num_malicious=int(job["malicious"]), attack="additive",
            attack_kwargs=(("delta", float(job["delta"])),))
    step, _ = steps.make_train_step_gspmd(model, par, opt_cfg, mesh, byz,
                                          k_agents=int(job["agents"]))
    template = jax.eval_shape(lambda: M.init_model(jax.random.key(0), model))
    return jax.jit(step, donate_argnums=(0, 1)), opt_cfg, template, mesh


def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


@jax.jit
def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def _diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x - y)))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def gaps(prog: dict, ref: dict) -> dict:
    """The compared numbers.  ``loss_gap``: the widest |loss - reference|
    over the checked steps.  ``grad_gap`` and ``change_gap``: the worst
    leaf's |norm - reference norm| over the larger of that leaf's and the
    median leaf's reference norm, for the first clipped gradient and for
    the parameters' change.  Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone and are left
    out of ``change_gap``."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    g_ref = ref["grad_norms"]
    med_g = float(np.median(list(g_ref.values())))
    grad_gap = max(abs(prog["grad_norms"][p] - g_ref[p]) / max(g_ref[p], med_g)
                   for p in g_ref)
    moved = [p for p in g_ref if g_ref[p] >= 1e-3 * med_g]
    c_ref = ref["change_norms"]
    med_c = float(np.median([c_ref[p] for p in moved]))
    change_gap = max(abs(prog["change_norms"][p] - c_ref[p])
                     / max(c_ref[p], med_c) for p in moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap}


@dataclasses.dataclass
class Program:
    """The program's side of the first steps."""
    losses: list
    grad_norms: dict
    change_norms: dict


def initial_params(seed, shapes, paths, treedef, mesh):
    """The seed's weights in the program's tree, replicated on the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    p = weights.make_params(seed, shapes)
    tree = jax.tree.unflatten(treedef, [p[q] for q in paths])
    del p
    if mesh.devices.size > 1:
        tree = jax.device_put(tree, NamedSharding(mesh, P()))
    return tree


def token_feed(seed, rows, cols, vocab, mesh):
    tok = weights.token_fn(seed, rows, cols, vocab)
    if mesh.devices.size == 1:
        return tok
    from jax.sharding import NamedSharding, PartitionSpec as P
    repl = NamedSharding(mesh, P())
    return lambda i: jax.device_put(tok(i), repl)


def first_steps(jstep, params, opt, feed, shapes, seed, n: int, b1: float,
                paths, mesh):
    """Drive the compiled step through its first ``n`` steps, capturing
    what the comparison reads before the state is donated onward."""
    losses, g1 = [], None
    for i in range(n):
        params, opt, met = jstep(params, opt, {"tokens": feed(i)})
        losses.append(met["loss"])
        if i == 0:
            g1 = [float(x) / (1.0 - b1) for x in _leaf_norms(opt.m)]
    p0 = initial_params(seed, shapes, paths, jax.tree.structure(params), mesh)
    change = [float(x) for x in _diff_norms(params, p0)]
    del p0
    prog = Program(losses=[float(x) for x in losses],
                   grad_norms=dict(zip(paths, g1)),
                   change_norms=dict(zip(paths, change)))
    return params, opt, prog


def job_of(cell) -> dict:
    """The deployment's job (agents, optimizer) from the configuration,
    with the traffic's batch, attack and aggregation over it."""
    return dict(cell.config["job"], **cell.traffic)


class Job:
    """One cell's step, built once: the compiled step and what makes its
    inputs for a seed."""

    def __init__(self, cell, devices):
        self.cfg = cell.config
        self.job = job_of(cell)
        job = self.job
        self.rows = int(job["agents"]) * int(job["seqs_per_agent"])
        self.seq = int(job["seq_len"])
        self.n_check = int(job["check_steps"])
        step, self.opt_cfg, template, self.mesh = build_step(
            dict(self.cfg, seq_len=self.seq), job, devices)
        self._jit = step
        self.compiled = None
        flat, self.treedef = jax.tree_util.tree_flatten_with_path(template)
        self.paths = [_path(kp) for kp, _ in flat]
        self.shapes = {p: (tuple(x.shape), str(x.dtype))
                       for p, (_, x) in zip(self.paths, flat)}

    def start(self, seed: int):
        """The seed's weights, optimizer state and token feed; compiles
        the step on first use.  Returns (params, opt, feed, compile_s)."""
        from repro.optim import optimizers
        feed = token_feed(seed, self.rows, self.seq + 1,
                          self.cfg["vocab_size"], self.mesh)
        params = initial_params(seed, self.shapes, self.paths, self.treedef,
                                self.mesh)
        opt = jax.jit(lambda t: optimizers.init(self.opt_cfg, t))(params)
        compile_s = 0.0
        if self.compiled is None:
            t0 = time.perf_counter()
            self.compiled = self._jit.lower(
                params, opt, {"tokens": feed(0)}).compile()
            compile_s = time.perf_counter() - t0
        return params, opt, feed, compile_s

    def first_steps(self, params, opt, feed, seed):
        return first_steps(self.compiled, params, opt, feed, self.shapes,
                           seed, self.n_check, self.opt_cfg.beta1,
                           self.paths, self.mesh)


def run(ctx):
    jb = Job(ctx.cell, ctx.devices)
    cfg, job = jb.cfg, jb.job
    params, opt, feed, compile_s = jb.start(ctx.seed)
    jstep = jb.compiled
    ctx.log(f"# step compile {compile_s:.2f} s; {job['agents']} agents x "
            f"{job['seqs_per_agent']} x {jb.seq} tokens, aggregation "
            f"{job['aggregation']} kernel {job['use_kernel']}")
    n_check = jb.n_check
    params, opt, prog = jb.first_steps(params, opt, feed, ctx.seed)
    jax.block_until_ready((params, opt))
    setup_s = time.perf_counter() - ctx.t_process

    ctx.start_trace()
    losses, done = [], []
    inflight = collections.deque()
    i = n_check
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - t0 < ctx.seconds:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                params, opt, met = jstep(params, opt, {"tokens": feed(i)})
            losses.append(met["loss"])
            inflight.append(met["loss"])
            if len(inflight) >= 2:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    inflight.popleft().block_until_ready()
                done.append(time.perf_counter())
            i += 1
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready((params, opt))
    window_s = time.perf_counter() - t0
    ctx.stop_trace()

    n = len(losses)
    loss_v = np.asarray(jax.device_get(losses), np.float64)
    final_ok = bool(jax.jit(lambda t: jnp.all(jnp.asarray(
        [jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(t)])))(params))
    failed = int(np.sum(~np.isfinite(loss_v))) + (0 if final_ok else 1)
    failed = min(failed, n)
    from bench.run import memory_peak_bytes
    peak = memory_peak_bytes(ctx.devices)
    ctx.log(f"# window {window_s:.3f} s, {n} steps, losses "
            f"{loss_v[:3].round(4).tolist()} .. {loss_v[-3:].round(4).tolist()}")
    gaps_ms = np.diff(np.asarray([t0] + done)) * 1e3
    if gaps_ms.size:
        ctx.log(f"# steps done every {np.median(gaps_ms):.2f} ms (median), "
                f"slowest {gaps_ms.max():.2f} ms at window step "
                f"{int(gaps_ms.argmax())}, first {gaps_ms[0]:.2f} ms")
    del params, opt, jstep, met, inflight, losses, jb
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference.run(cfg, job, ctx.seed, n_check)
    ctx.log(f"# reference {n_check} steps {time.perf_counter() - t_ref:.2f} s")
    g = gaps(dataclasses.asdict(prog), ref)
    ctx.log(f"# program losses {prog.losses} reference {ref['losses']}")
    limits = job["limits"]

    step_s = window_s / max(n, 1)
    seq = int(job["seq_len"])
    k = int(job["agents"])
    facts = {
        "steps": n, "window_s": window_s, "step_s": step_s,
        "chips": len(ctx.devices), "device_kind": ctx.devices[0].device_kind,
        "flops_per_step": work.train_flops(
            cfg, k * int(job["seqs_per_agent"]) * seq, seq),
        "agg_bytes_per_step": (work.mm_bytes(k, work.aggregated_coords(cfg))
                               if job["aggregation"] != "mean" else None),
        "aggregation": job["aggregation"], "use_kernel": job["use_kernel"],
    }
    from bench.run import DriverResult
    return DriverResult(
        metrics={"setup_s": setup_s, "step_ms": step_s * 1e3},
        checks={name: (g[name], limits[name]) for name in g},
        attempted=n, failed=failed, memory_peak_bytes=peak, facts=facts,
        correct=failed == 0 and n > 0 and all(math.isfinite(v)
                                              for v in g.values()))


def calibrate(cell, devices, seeds, control_seeds, log):
    """Readings that the limits are set from: the program against the
    reference on ``seeds``; on ``control_seeds`` the control (the
    reference with int8 matmul operands) and the planted faults (half
    the batch; for a cell on several chips, no exchange between them),
    each put in the program's place.  A seed only in ``control_seeds``
    runs the references alone, which need one chip."""
    jb = Job(cell, devices) if seeds else None
    cfg = cell.config
    job = job_of(cell)
    n = int(job["check_steps"])
    out = []
    for seed in list(seeds) + [s for s in control_seeds if s not in seeds]:
        ref = None
        if seed in seeds:
            params, opt, feed, _ = jb.start(seed)
            params, opt, prog = jb.first_steps(params, opt, feed, seed)
            del params, opt
            jb.compiled = None          # as in a run: freed before the reference
            gc.collect()
            t0 = time.perf_counter()
            ref = reference.run(cfg, job, seed, n)
            row = {"seed": seed, "kind": "program",
                   "ref_s": time.perf_counter() - t0,
                   **gaps(dataclasses.asdict(prog), ref)}
            log(row)
            out.append(row)
        if seed in control_seeds:
            ref = ref or reference.run(cfg, job, seed, n)
            others = [("control_int8", {"matmul": "int8"}),
                      ("fault_half_batch", {"fault": "half_batch"})]
            if cell.chips > 1:
                others.append(("fault_no_exchange", {"fault": "no_exchange"}))
            for kind, kw in others:
                other = reference.run(cfg, job, seed, n, **kw)
                row = {"seed": seed, "kind": kind, **gaps(other, ref)}
                log(row)
                out.append(row)
    return out
