"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) pair
against ShapeDtypeStruct inputs on 512 placeholder host devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all [--multi-pod]
  ... writes one JSON per pair under experiments/dryrun/.
"""

# The VERY FIRST lines, before any other import: jax locks the device
# count (and platform) on first init.  Dry-run only, on host devices --
# tests/benches must see 1 device, and a chip is never touched.
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch import steps
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.optim import optimizers

COLLECTIVE_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\(")

DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
    "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2,
}


def collective_stats(hlo_text: str) -> dict:
    """Sum result-buffer bytes of every collective op in the compiled HLO.

    Buffer sizes are per-device (the module is the per-device SPMD
    program).  Returns {op_kind: {"count": n, "bytes": b}, ...}."""
    stats: dict = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        b = n * DTYPE_BYTES[dtype]
        s = stats.setdefault(kind, {"count": 0, "bytes": 0})
        s["count"] += 1
        s["bytes"] += b
    return stats


def _as_sds(template, specs, mesh, dtype_map=None):
    def one(leaf, spec):
        dt = leaf.dtype
        if dtype_map and jnp.issubdtype(dt, jnp.floating):
            dt = dtype_map
        return jax.ShapeDtypeStruct(
            leaf.shape, dt,
            sharding=jax.sharding.NamedSharding(mesh, spec))
    return jax.tree.map(one, template, specs,
                        is_leaf=lambda x: hasattr(x, "shape"))


def lower_pair(arch_id: str, shape_name: str, multi_pod: bool,
               aggregation: str | None = None):
    """Lower + compile one (arch, shape, mesh) pair; return the record."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    arch = configs.load_arch(arch_id)
    shape = configs.INPUT_SHAPES[shape_name]
    model = configs.model_for_shape(arch.model, shape)
    par = arch.parallel_for(shape.name)
    if aggregation:
        par = dataclasses.replace(par, aggregation=aggregation)
    opt_cfg = optimizers.OptimizerConfig(state_dtype=par.opt_state_dtype)

    t0 = time.time()
    if shape.kind == "train":
        template = jax.eval_shape(lambda: M.init_model(jax.random.key(0), model))
        opt_t = jax.eval_shape(lambda: optimizers.init(opt_cfg, template))
        batch_t = configs.input_specs(model, shape)["batch"]
        if par.fsdp:
            build, pspecs = steps.make_train_step_fsdp(model, par, opt_cfg, mesh)
            step = build(batch_t)
        else:
            step, pspecs = steps.make_train_step_gspmd(model, par, opt_cfg, mesh)
        ospecs = steps.opt_specs(opt_t, pspecs)
        bspecs = steps.batch_specs(batch_t, mesh)
        args = (
            _as_sds(template, pspecs, mesh),
            _as_sds(opt_t, ospecs, mesh),
            _as_sds(batch_t, bspecs, mesh),
        )
        fn = jax.jit(step, donate_argnums=(0, 1))
    elif shape.kind == "prefill":
        template = jax.eval_shape(lambda: M.init_model(jax.random.key(0), model))
        pspecs = steps.param_specs(template, mesh, fsdp=par.fsdp)
        batch_t = configs.input_specs(model, shape)["batch"]
        bspecs = steps.batch_specs(batch_t, mesh)
        step = steps.make_prefill_step(model, mesh, fsdp=par.fsdp,
                                       batch_template=batch_t)
        args = (
            _as_sds(template, pspecs, mesh, dtype_map=jnp.dtype(model.act_dtype)),
            _as_sds(batch_t, bspecs, mesh),
        )
        fn = jax.jit(step)
    else:  # decode
        template = jax.eval_shape(lambda: M.init_model(jax.random.key(0), model))
        pspecs = steps.param_specs(template, mesh, fsdp=par.fsdp)
        ins = configs.input_specs(model, shape)
        tok_t, cache_t = ins["tokens"], ins["cache"]
        cspecs = steps.cache_specs(model, cache_t, mesh, shape.global_batch)
        tspec = steps.batch_specs({"t": tok_t}, mesh)["t"]
        step = steps.make_decode_step(model, mesh, fsdp=par.fsdp,
                                      cache_template=cache_t,
                                      global_batch=shape.global_batch)
        args = (
            _as_sds(template, pspecs, mesh, dtype_map=jnp.dtype(model.act_dtype)),
            _as_sds({"t": tok_t}, {"t": tspec}, mesh)["t"],
            _as_sds(cache_t, cspecs, mesh),
        )
        fn = jax.jit(step, donate_argnums=(2,))

    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_stats(hlo)

    rec = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
        "aggregation": par.aggregation if shape.kind == "train" else None,
        "fsdp": par.fsdp,
        "microbatches": par.microbatches if shape.kind == "train" else None,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "params": model.param_count(),
        "active_params": model.active_param_count(),
        "flops_per_device": cost.get("flops") if cost else None,
        "bytes_accessed_per_device": cost.get("bytes accessed") if cost else None,
        "collectives": coll,
        "memory": None,
        "hlo_bytes": len(hlo),
    }
    if mem is not None:
        rec["memory"] = {
            k: getattr(mem, k)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes")
            if hasattr(mem, k)
        }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--aggregation", default=None,
                    help="override train aggregation (mean|gather_mm|rs_mm)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    archs = list(configs.ARCH_IDS) if args.arch == "all" \
        else [configs.resolve_arch(args.arch)]
    shapes = list(configs.INPUT_SHAPES) if args.shape == "all" \
        else [args.shape]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for a in archs:
        for s in shapes:
            meshname = "2x16x16" if args.multi_pod else "16x16"
            tag = f"_{args.tag}" if args.tag else ""
            path = os.path.join(args.out, f"{a}_{s}_{meshname}{tag}.json")
            t0 = time.time()
            try:
                rec = lower_pair(a, s, args.multi_pod, args.aggregation)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                mem = rec["memory"] or {}
                print(f"OK   {a:24s} {s:12s} {meshname:8s} "
                      f"compile={rec['compile_s']:7.1f}s "
                      f"flops/dev={rec['flops_per_device'] or 0:.3e} "
                      f"temp={mem.get('temp_size_in_bytes', 0)/2**30:7.2f}GiB",
                      flush=True)
            except Exception as e:  # noqa: BLE001 -- report and continue
                failures.append((a, s))
                print(f"FAIL {a:24s} {s:12s} {meshname:8s} "
                      f"({time.time()-t0:.0f}s): {type(e).__name__}: "
                      f"{str(e)[:200]}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print("\nall pairs lowered + compiled OK")


if __name__ == "__main__":
    main()
