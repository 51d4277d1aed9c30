"""Distributed step builders: the paper's aggregation as a first-class
feature of the training loop, plus serving steps.

Two train-step modes (see DESIGN.md §4):

  Mode A -- replicated params (small/mid archs).  Pure GSPMD jit:
    per-agent gradients via vmap over the agent axis of the batch, then
    *constraint-driven* robust aggregation -- the rs_mm lowering is two
    with_sharding_constraint calls (K-sharded -> M-sharded is an
    all-to-all; the result constraint is the all-gather), so the
    collective schedule is visible and tunable in the HLO.

  Mode B -- FSDP (archs whose params/optimizer don't fit replicated).
    shard_map manual over the agent axes ('pod','data'), GSPMD-auto over
    'model'.  Block params are stored sharded on an fsdp dim; each scan
    step all-gathers its layer through ``fsdp_gather_robust`` whose
    custom VJP replaces the usual reduce-scatter(sum) with the robust
    all_to_all + MM + keep-own-shard scatter.  Aggregation therefore
    happens per (layer x microbatch) -- elementwise, so identical
    statistics per coordinate; see DESIGN.md for the microbatch nuance.

Serve steps (prefill / decode) are always plain GSPMD jit -- no
aggregation in inference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.configs.base import ModelConfig, ParallelConfig
from repro.core import attacks as attacks_lib
from repro.core import sharded as sharded_lib
from repro.launch import sharding
from repro.launch.mesh import agent_axes, num_agents
from repro.models import model as M
from repro.optim import optimizers

# roots whose stacked leaves are scanned (and hence fsdp-hookable)
SCAN_DIMS = {"blocks": 1, "enc_blocks": 1, "mamba_groups": 2}

# ``jax.named_scope`` names of the Mode A step's phases, in order.  They
# reach every compiled instruction's ``op_name`` metadata (and so the
# profiler's op view); the backward of ``agent_grads`` carries
# ``transpose(`` in its ``op_name``.
STEP_PHASES = ("agent_grads", "attack", "aggregate", "optimizer")


# ===========================================================================
# parameter / optimizer / batch / cache specs
# ===========================================================================

def _path_root(path) -> str:
    p = path[0]
    return str(getattr(p, "key", getattr(p, "idx", p)))


def _shardable(dim: int, size: int) -> bool:
    """Evenly divisible, or big enough that GSPMD padding waste is <13%
    (uneven shardings are legal and padded; used for e.g. odd vocabs)."""
    return dim % size == 0 or dim >= 8 * size


def shard_dims(sliced_shape, fsdp_size: int, model_size: int):
    """(fsdp_dim, model_dim) for a *sliced* (per-layer) leaf.

    The MODEL dim is chosen FIRST (largest divisible dim; the expert dim
    for 3D expert tensors) so tensor parallelism follows the Megatron
    col/row pattern -- choosing the fsdp dim first pushed 'model' onto
    w_down's OUTPUT dim, which broke row-parallelism and made SPMD
    all-gather the full (B, S, d_ff) hidden activation (12 GiB f32 on
    qwen1.5-110b prefill).  The fsdp dim is the first remaining
    divisible dim.  1D leaves prefer fsdp (they must be hooked so their
    gradients go through the robust scatter).
    """
    nd = len(sliced_shape)
    if nd == 1:
        if fsdp_size > 1 and sliced_shape[0] % fsdp_size == 0:
            return 0, -1
        if model_size > 1 and sliced_shape[0] % model_size == 0:
            return -1, 0
        return -1, -1
    # model dim
    md = -1
    if model_size > 1:
        if nd == 3 and sliced_shape[0] % model_size == 0:
            md = 0  # expert parallelism
        else:
            best_sz = 0
            for i in range(nd):
                if _shardable(sliced_shape[i], model_size) \
                        and sliced_shape[i] >= best_sz:
                    md, best_sz = i, sliced_shape[i]
    # fsdp dim: first divisible dim that is not the model dim
    fd = -1
    if fsdp_size > 1:
        for i in range(nd):
            if i != md and sliced_shape[i] % fsdp_size == 0:
                fd = i
                break
    return fd, md


def fsdp_dim_for(sliced_shape, fsdp_size: int, model_size: int = 1) -> int:
    return shard_dims(sliced_shape, fsdp_size, model_size)[0]


def param_specs(template, mesh, fsdp: bool):
    """Full PartitionSpecs (manual + model axes) for every param leaf."""
    model_size = mesh.shape.get("model", 1)
    ax = agent_axes(mesh)
    fsdp_size = num_agents(mesh) if fsdp else 1

    def spec(path, leaf):
        root = _path_root(path)
        nd = len(leaf.shape)
        entries: list = [None] * nd
        if root == "embed":
            if model_size > 1 and _shardable(leaf.shape[0], model_size):
                entries[0] = "model"
            return P(*entries)
        scan_dims = SCAN_DIMS.get(root, 0)
        sliced = leaf.shape[scan_dims:]
        fd, md = shard_dims(sliced, fsdp_size if root in SCAN_DIMS else 1,
                            model_size)
        if fd >= 0:
            entries[scan_dims + fd] = ax if len(ax) > 1 else ax[0]
        if md >= 0:
            entries[scan_dims + md] = "model"
        return P(*entries)

    return jax.tree_util.tree_map_with_path(spec, template)


def manual_only(specs, mesh):
    """Strip non-manual axes from specs (for shard_map in/out_specs)."""
    keep = set(agent_axes(mesh))

    def strip(p):
        out = []
        for e in p:
            if e is None:
                out.append(None)
            elif isinstance(e, tuple):
                kept = tuple(a for a in e if a in keep)
                out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
            else:
                out.append(e if e in keep else None)
        return P(*out)

    return jax.tree.map(strip, specs, is_leaf=lambda x: isinstance(x, P))


def opt_specs(opt_state, pspecs):
    """Optimizer-state specs mirroring the param specs."""
    def spec(path, leaf):
        # m / v live under fields named 'm'/'v' with param-tree structure
        root = str(getattr(path[0], "name", getattr(path[0], "idx", path[0])))
        if leaf.ndim == 0:
            return P()
        # walk the param specs with the remaining path
        node = pspecs
        for p in path[1:]:
            key = getattr(p, "key", getattr(p, "idx", None))
            node = node[key]
        return node
    return jax.tree_util.tree_map_with_path(spec, opt_state)


def batch_specs(batch_template, mesh):
    ax = agent_axes(mesh)
    a = ax if len(ax) > 1 else ax[0]

    def spec(leaf):
        e: list = [None] * len(leaf.shape)
        ktot = num_agents(mesh)
        if leaf.shape[0] % ktot == 0:
            e[0] = a
        return P(*e)

    return jax.tree.map(spec, batch_template)


def cache_specs(model_cfg: ModelConfig, cache_template, mesh, global_batch: int):
    """Specs for decode caches: batch over agent axes, heads/head_dim
    over model (with divisibility fallback)."""
    model_size = mesh.shape.get("model", 1)
    ax = agent_axes(mesh)
    a = ax if len(ax) > 1 else ax[0]
    ktot = num_agents(mesh)

    def spec(path, leaf):
        sh = leaf.shape
        entries: list = [None] * len(sh)
        # batch dim: the first dim whose size == global_batch (stacked
        # caches put L/G first); only shard if divisible by agents
        for i, d in enumerate(sh):
            if d == global_batch:
                if d % ktot == 0:
                    entries[i] = a
                bdim = i
                break
        else:
            return P(*entries)
        # shard one later dim over model: prefer kv/heads, then head_dim
        for i in range(len(sh) - 1, bdim, -1):
            if sh[i] >= model_size and sh[i] % model_size == 0 and model_size > 1:
                entries[i] = "model"
                break
        return P(*entries)

    return jax.tree_util.tree_map_with_path(spec, cache_template)


def to_named(specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ===========================================================================
# Mode A: constraint-driven robust aggregation over stacked agent grads
# ===========================================================================

def _mm_axis0(flat, num_iters: int, use_kernel: bool = False):
    """All MM aggregation in the train steps resolves through the one
    shared path (core.sharded.engine_aggregator -> kernels.ops), the
    same resolution the scenario runner and the shard_map collectives
    use; ``use_kernel`` (ParallelConfig.use_kernel) selects the fused
    Pallas kernel, else the structure-preserving jnp backend (identical
    estimator).  Kernel tile sizes resolve per (K, M, dtype) through
    kernels.tuning -- pre-running ``tuning.autotune`` for the step's
    gradient shapes makes every launch here use the measured winner
    instead of the VMEM heuristic."""
    agg = sharded_lib.engine_aggregator(
        "mm_pallas" if use_kernel else "mm_tukey", num_iters=num_iters)
    return agg(flat, None)


def _mm_axis0_laid_out(g, spec: P, mesh, par: ParallelConfig):
    """MM over axis 0 of ``g``, which is laid out as ``spec`` (axis 0
    unsharded).  GSPMD cannot partition a Pallas kernel, so every device
    aggregates its own coordinate shard inside shard_map -- exact for
    both backends, since the estimate is elementwise."""
    return compat.shard_map(
        lambda t: _mm_axis0(t, par.agg_num_iters, par.use_kernel),
        mesh=mesh, in_specs=spec, out_specs=P(*spec[1:]))(g)


def aggregate_stack(grads, mesh, par: ParallelConfig,
                    out_specs, agg_axes) -> dict:
    """Aggregate per-agent gradient pytrees (leaves (K, ...)) into one.

    method:
      mean       -> jnp.mean over axis 0 (lowered by GSPMD to an all-reduce)
      gather_mm  -> K replicated over agent axes (all-gather), full MM
                    everywhere (paper-faithful baseline)
      rs_mm      -> all_to_all reshard so every device owns the full K
                    column for an M/(K*model) slice; MM locally; the
                    output constraint restores the param sharding
                    (all-gather).  Wire cost of a mean all-reduce.
    """
    method = par.aggregation
    leaves, treedef = jax.tree.flatten(grads)
    out_leaves = jax.tree.leaves(out_specs, is_leaf=lambda x: isinstance(x, P))
    k = leaves[0].shape[0]
    k_agents = num_agents(mesh)
    a_entry = agg_axes if len(agg_axes) > 1 else agg_axes[0]

    def rs_target(leaf, ospec):
        """Reshard target: agent dim K local, coords sharded -- put the
        agent mesh axes on the first free dim divisible by K (keeping the
        leaf UNFLATTENED so the model-axis sharding survives; flattening
        forces SPMD to replicate).  None if no dim qualifies."""
        entries = [None] + list(ospec) + [None] * (leaf.ndim - 1 - len(ospec))
        for i in range(1, leaf.ndim):
            if entries[i] is None and leaf.shape[i] % k_agents == 0:
                entries[i] = a_entry
                return P(*entries)
        return None

    def one(leaf, ospec):
        if method == "mean":
            est = jnp.mean(leaf.astype(jnp.float32), axis=0)
        elif method == "hier_mm" and "pod" in mesh.shape:
            # two-level ablation: MM within each pod's agents, then
            # arithmetic mean across pods.  Confines the robust reshard
            # to intra-pod ICI; breakdown guarantees hold per pod.
            n_pods = mesh.shape["pod"]
            g = leaf.astype(jnp.float32).reshape(
                (n_pods, k // n_pods) + leaf.shape[1:])
            spec = rs_target(leaf, ospec)
            if spec is not None:
                # rs_target used the joint ('pod','data') agent entry;
                # within-pod resharding uses 'data' only ('pod' now
                # shards the pod axis of the stack)
                inner = [("data" if (e == ("pod", "data") or e == "pod"
                                     or e == "data") else e)
                         for e in spec[1:]]
                g = jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, P("pod", None, *inner)))
            else:
                inner = []
            pod_est = _mm_axis0_laid_out(jnp.moveaxis(g, 0, 1),
                                         P(None, "pod", *inner), mesh, par)
            est = jnp.mean(pod_est, axis=0)
        else:
            g = leaf.astype(jnp.float32)
            if method in ("rs_mm", "hier_mm"):
                spec = rs_target(leaf, ospec)
                if spec is None:   # tiny/odd leaf: gather pattern instead
                    spec = P(None, *ospec)
            elif method == "gather_mm":
                spec = P(None, *ospec)
            else:
                raise ValueError(f"unknown aggregation {method!r}")
            g = jax.lax.with_sharding_constraint(g, NamedSharding(mesh, spec))
            est = _mm_axis0_laid_out(g, spec, mesh, par)
        est = est.astype(leaf.dtype)
        return jax.lax.with_sharding_constraint(
            est, NamedSharding(mesh, ospec))

    return jax.tree.unflatten(
        treedef, [one(l, s) for l, s in zip(leaves, out_leaves)])


def grad_consensus(grads, benign: jnp.ndarray) -> jnp.ndarray:
    """Mean squared distance of the benign agents' stacked per-agent
    gradients (leaves (K, ...)) from their benign centroid, summed over
    leaves -- the pre-aggregation disagreement the robust estimator has
    to resolve.  The scenario runner reports this as the substrate
    paradigm's ``consensus`` metric (a single shared model has no
    per-agent parameter spread)."""
    bf = benign.astype(jnp.float32)
    nb = jnp.maximum(jnp.sum(bf), 1.0)
    total = jnp.zeros((), jnp.float32)
    for g in jax.tree.leaves(grads):
        gf = g.astype(jnp.float32)
        bm = bf.reshape((bf.shape[0],) + (1,) * (gf.ndim - 1))
        centroid = jnp.sum(gf * bm, axis=0) / nb
        sq = jnp.sum((gf - centroid[None]) ** 2,
                     axis=tuple(range(1, gf.ndim)))
        total = total + jnp.sum(sq * bf)
    return total / nb


def make_train_step_gspmd(model_cfg: ModelConfig, par: ParallelConfig,
                          opt_cfg: optimizers.OptimizerConfig, mesh,
                          byzantine: Optional[attacks_lib.ByzantineConfig] = None,
                          k_agents: Optional[int] = None,
                          consensus_metric: bool = False):
    """Mode A train step.  Signature: (params, opt_state, batch) ->
    (params, opt_state, metrics).

    ``k_agents`` overrides the mesh-derived agent count: the scenario
    substrate (and single-host simulation generally) runs K aggregation
    agents on fewer devices -- the agent-axis sharding constraints
    degrade to (padded) no-ops and the aggregation statistics are
    identical to a K-device mesh.  The step is a pure function of
    ``(params, opt_state, batch)``, so it is scan-compatible: the
    scenario runner scans exactly this body (see scenarios.substrate).

    ``consensus_metric`` adds ``grad_consensus`` over the benign
    per-agent gradient stacks to the metrics dict.  Opt-in: it is a
    full extra f32 pass over the (K, param) stacks, so the production
    train loop (which never reads it) should not pay for it.
    """
    if k_agents is None:
        k_agents = num_agents(mesh)
    ax = agent_axes(mesh)
    template = jax.eval_shape(
        lambda: M.init_model(jax.random.key(0), model_cfg))
    pspecs = param_specs(template, mesh, fsdp=False)

    def step(params, opt_state, batch):
        # batch rule stripped: inside the per-agent vmap the model's
        # 'batch' constraints would grab pod/data for the (small)
        # per-agent batch dim, forcing SPMD to replicate the vmapped
        # agent dim instead (observed 18.6 GiB stacks on 2x16x16).
        with sharding.use_mesh(mesh, {"batch": ()}):
            def to_agents(leaf):
                t = leaf.reshape((k_agents, leaf.shape[0] // k_agents)
                                 + leaf.shape[1:])
                spec = P(ax if len(ax) > 1 else ax[0])
                return jax.lax.with_sharding_constraint(
                    t, NamedSharding(mesh, spec))

            nm = par.microbatches

            def constrain_like_params(tree):
                # keep the (per-agent) grad accumulator model-sharded; the
                # vmapped agent dim is sharded by the post-vmap constraint.
                t_leaves, t_def = jax.tree.flatten(tree)
                s_leaves = jax.tree.leaves(
                    pspecs, is_leaf=lambda x: isinstance(x, P))
                t_leaves = [
                    jax.lax.with_sharding_constraint(
                        t, NamedSharding(mesh, sp))
                    for t, sp in zip(t_leaves, s_leaves)]
                return jax.tree.unflatten(t_def, t_leaves)

            def agent_grad(b):
                nm_ = min(nm, jax.tree.leaves(b)[0].shape[0])
                if nm_ == 1:
                    return jax.value_and_grad(
                        lambda p: M.loss_fn(p, model_cfg, b, remat=par.remat)
                    )(params)
                mb = jax.tree.map(
                    lambda t: t.reshape((nm_, t.shape[0] // nm_) + t.shape[1:]),
                    b)

                def micro(carry, one):
                    loss, g = jax.value_and_grad(
                        lambda p: M.loss_fn(p, model_cfg, one, remat=par.remat)
                    )(params)
                    acc = jax.tree.map(jnp.add, carry, g)
                    return constrain_like_params(acc), loss

                zeros = constrain_like_params(jax.tree.map(
                    lambda t: jnp.zeros(t.shape, jnp.float32), params))
                gsum, losses = jax.lax.scan(micro, zeros, mb)
                g = jax.tree.map(lambda t: t / nm_, gsum)
                return jnp.mean(losses), g

            with jax.named_scope("agent_grads"):
                ab = jax.tree.map(to_agents, batch)
                losses, grads = jax.vmap(agent_grad)(ab)  # leaves: (K, ...)

                # keep the per-agent stacks K-sharded over the agent axes
                # and model-sharded like their params (SPMD would
                # otherwise replicate the (K, full-param) f32 stacks).
                a_entry = ax if len(ax) > 1 else ax[0]
                g_leaves, g_def = jax.tree.flatten(grads)
                sp_leaves = jax.tree.leaves(
                    pspecs, is_leaf=lambda x: isinstance(x, P))
                g_leaves = [
                    jax.lax.with_sharding_constraint(
                        g, NamedSharding(mesh, P(a_entry, *sp)))
                    for g, sp in zip(g_leaves, sp_leaves)]
                grads = jax.tree.unflatten(g_def, g_leaves)

            if byzantine is not None and byzantine.num_malicious > 0:
                with jax.named_scope("attack"):
                    key = jax.random.fold_in(jax.random.key(17),
                                             opt_state.step)
                    grads = byzantine.apply_tree(grads, key, opt_state.step)

            with jax.named_scope("aggregate"):
                agg = aggregate_stack(grads, mesh, par, pspecs, ax)

            with jax.named_scope("optimizer"):
                new_params, new_opt = optimizers.update(opt_cfg, params, agg,
                                                        opt_state)
                metrics = {"loss": jnp.mean(losses),
                           "grad_norm": optimizers.global_norm(agg)}
                if consensus_metric:
                    if byzantine is not None and byzantine.num_malicious > 0:
                        benign = ~byzantine.malicious_mask(k_agents,
                                                           opt_state.step)
                    else:
                        benign = jnp.ones((k_agents,), bool)
                    metrics["consensus"] = grad_consensus(grads, benign)
            return new_params, new_opt, metrics

    return step, pspecs


# ===========================================================================
# Mode B: FSDP with robust-scatter custom VJP
# ===========================================================================

GATHER_DTYPE = jnp.bfloat16   # compute copy of gathered layer params
_MM_CHUNK_BYTES = 64 * 2 ** 20


def model_only_spec(spec: P) -> P:
    """Strip everything except the 'model' axis from a PartitionSpec."""
    out = []
    for e in spec:
        if e == "model":
            out.append("model")
        elif isinstance(e, tuple) and "model" in e:
            out.append("model")
        else:
            out.append(None)
    return P(*out)


def constrain_auto(x, spec: P):
    """Sharding constraint on the auto ('model') axes from inside a
    manual shard_map region.  CRITICAL for memory: without it SPMD
    replicates gathered layer params / cotangents across the model axis
    (observed: full 3.9 GiB expert tensors per device on dbrx)."""
    if all(e is None for e in spec):
        return x
    am = compat.get_abstract_mesh()
    return jax.lax.with_sharding_constraint(x, NamedSharding(am, spec))


def _model_manual(fn, in_spec: P, out_spec: P):
    """Wrap ``fn`` in an inner shard_map that manualizes the 'model' axis.

    Manual collectives (all_gather/all_to_all over the agent axes) used
    directly on auto-sharded operands force SPMD to first all-gather the
    model axis -- observed as full 3.9 GiB per-device expert tensors on
    dbrx.  Running them inside a nested model-manual region keeps every
    buffer model-sharded end to end."""
    am = compat.get_abstract_mesh()
    if am is None or am.shape.get("model", 1) <= 1:
        return fn
    return compat.shard_map(fn, in_specs=in_spec, out_specs=out_spec,
                            axis_names={"model"}, check_vma=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6, 7))
def fsdp_gather_robust(w, dim: int, axes: tuple, method: str,
                       num_iters: int, byz: tuple, mspec: P,
                       use_kernel: bool = False):
    """FSDP layer gather with a robust-aggregating backward.

    fwd: all-gather the f32 master shard as bf16 (halves ICI traffic and
    the gathered residency; model code casts to act dtype anyway), run
    inside a model-manual region so the gather never replicates the
    model axis.
    bwd: instead of the usual reduce-scatter(sum), a robust scatter --
    all_to_all so each agent owns the full K-column of its shard, then a
    *chunked* MM fixed point (bounding the f32 sort/IRLS temporaries to
    ~64MB instead of full-gradient-sized buffers), returning the f32
    shard gradient.
    """
    def gather_local(wl):
        return jax.lax.all_gather(wl.astype(GATHER_DTYPE), axes, axis=dim,
                                  tiled=True)
    return _model_manual(gather_local, mspec, mspec)(w)


def _fgr_fwd(w, dim, axes, method, num_iters, byz, mspec, use_kernel=False):
    # residual-free: master shards are always f32
    return fsdp_gather_robust(w, dim, axes, method, num_iters, byz,
                              mspec, use_kernel), None


def _chunked_mm_axis0(sw, num_iters, use_kernel: bool = False):
    """MM over axis 0 of (K, n0, ...) in chunks along n0 (keeps each f32
    temp <= _MM_CHUNK_BYTES; never flattens, so auto-axis sharding of
    trailing dims survives)."""
    k, n0 = sw.shape[0], sw.shape[1]
    rest = 1
    for d in sw.shape[2:]:
        rest *= d
    per_row = k * rest * 4
    target = max(1, _MM_CHUNK_BYTES // max(per_row, 1))
    c = 1
    for cand in range(min(target, n0), 0, -1):
        if n0 % cand == 0:
            c = cand
            break
    if c == n0:
        return _mm_axis0(sw.astype(jnp.float32), num_iters, use_kernel)
    sw2 = sw.reshape((k, n0 // c, c) + sw.shape[2:])
    sw2 = jnp.moveaxis(sw2, 1, 0)            # (n0/c, K, c, ...)
    est = jax.lax.map(
        lambda sl: _mm_axis0(sl.astype(jnp.float32), num_iters, use_kernel),
        sw2)
    return est.reshape((n0,) + sw.shape[2:])


def _fgr_bwd(dim, axes, method, num_iters, byz, mspec, use_kernel, _res, g):
    w_dtype = jnp.float32

    k = jax.lax.psum(1, axes)   # static (folds at trace time)
    # axis_index must be taken OUTSIDE the nested model-manual region
    # (sdy rejects re-binding the parent's manual axes inside it).
    if byz:
        cfg = attacks_lib.ByzantineConfig(**dict(byz))
        is_mal = jax.lax.axis_index(axes) >= k - cfg.num_malicious
    else:
        cfg, is_mal = None, jnp.asarray(False)

    def scatter_local(gl, mal):
        if cfg is not None:
            gl = attacks_lib.apply_local(gl, mal, cfg.attack,
                                         dict(cfg.attack_kwargs))
        if method == "mean":
            return (jax.lax.psum_scatter(
                gl.astype(jnp.float32), axes, scatter_dimension=dim,
                tiled=True) / k).astype(w_dtype)
        # robust scatter: every rank ends with the MM estimate of its own
        # shard.  Runs model-manual (see _model_manual) on intact dims.
        g2 = jnp.moveaxis(gl, dim, 0)
        sh = g2.shape
        g2 = g2.reshape((k, sh[0] // k) + sh[1:])
        sw = jax.lax.all_to_all(g2, axes, split_axis=0, concat_axis=0)
        est = _chunked_mm_axis0(sw, num_iters, use_kernel).astype(w_dtype)
        return jnp.moveaxis(est, 0, dim) if dim else est

    return (_model_manual(scatter_local, (mspec, P()), mspec)(g, is_mal),)


fsdp_gather_robust.defvjp(_fgr_fwd, _fgr_bwd)


def make_fsdp_hook(mesh, method: str, num_iters: int,
                   byzantine: Optional[attacks_lib.ByzantineConfig],
                   dims_tree, mspec_tree, use_kernel: bool = False):
    """``dims_tree`` mirrors the *sliced* block structure with the fsdp
    gather dim per leaf (-1 = not sharded).  It must be computed from the
    GLOBAL template shapes -- inside shard_map the leaves are local, and
    divisibility checks on local shapes would mis-fire (e.g. a (128,)
    qk-norm leaf is locally (8,) on 16 ranks).  ``mspec_tree`` carries
    the per-leaf model-axis PartitionSpec for the gathered value."""
    ax = agent_axes(mesh)
    byz = ()
    if byzantine is not None and byzantine.num_malicious > 0:
        byz = (("num_malicious", byzantine.num_malicious),
               ("attack", byzantine.attack),
               ("attack_kwargs", byzantine.attack_kwargs))

    def hook(blk):
        def one(w, d, ms):
            if d < 0:
                return w
            return fsdp_gather_robust(w, d, ax, method, num_iters, byz, ms,
                                      use_kernel)
        return jax.tree.map(one, blk, dims_tree, mspec_tree)

    return hook


def block_dims_tree(template_blocks, fsdp_size: int, model_size: int,
                    scan_dims: int = 1):
    tree = jax.tree.map(
        lambda leaf: fsdp_dim_for(leaf.shape[scan_dims:], fsdp_size,
                                  model_size),
        template_blocks)
    # Leaves without an fsdp dim (e.g. dbrx's (d, E=16) router on 32
    # agents) are left un-hooked; the train step aggregates their raw
    # per-agent gradients post-hoc (same path as embed/head).
    return tree


def block_mspec_tree(block_pspecs, scan_dims: int = 1):
    """Per-sliced-leaf model-only specs from the full param specs."""
    return jax.tree.map(
        lambda sp: model_only_spec(P(*sp[scan_dims:])),
        block_pspecs, is_leaf=lambda x: isinstance(x, P))


def make_train_step_fsdp(model_cfg: ModelConfig, par: ParallelConfig,
                         opt_cfg: optimizers.OptimizerConfig, mesh,
                         byzantine=None):
    """Mode B train step (dense/moe/vlm only -- the fsdp-flagged archs)."""
    assert model_cfg.arch_type in ("dense", "moe", "vlm"), model_cfg.arch_type
    ax = agent_axes(mesh)
    k_agents = num_agents(mesh)
    template = jax.eval_shape(lambda: M.init_model(jax.random.key(0), model_cfg))
    pspecs = param_specs(template, mesh, fsdp=True)
    mspecs = manual_only(pspecs, mesh)
    dims_tree = block_dims_tree(template["blocks"], k_agents,
                                mesh.shape.get("model", 1))
    mspec_tree = block_mspec_tree(pspecs["blocks"])
    hook = make_fsdp_hook(mesh, par.aggregation, par.agg_num_iters, byzantine,
                          dims_tree, mspec_tree, par.use_kernel)
    a = ax if len(ax) > 1 else ax[0]

    def local_step(params, opt_state, batch):
        with sharding.use_mesh(mesh, {"batch": (), "fsdp": ()}):
            # local batch may be smaller than the configured microbatch
            # count on bigger meshes (e.g. 256/32 agents = 8 local seqs)
            nm = min(par.microbatches, jax.tree.leaves(batch)[0].shape[0])

            def lossf(p, b):
                return M.loss_fn(p, model_cfg, b, layer_hook=hook,
                                 remat=par.remat)

            mb = jax.tree.map(
                lambda t: t.reshape((nm, t.shape[0] // nm) + t.shape[1:]),
                batch)

            def micro(carry, one):
                loss, g = jax.value_and_grad(lossf)(params, one)
                return jax.tree.map(jnp.add, carry, g), loss

            zeros = jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32),
                                 params)
            gsum, losses = jax.lax.scan(micro, zeros, mb)
            grads = jax.tree.map(lambda t: t / nm, gsum)

            # non-hooked roots (embed/head/norms): per-agent full grads ->
            # robust all-reduce over the agent axes, exactly as in Mode A.
            hooked = {r for r in grads if r in SCAN_DIMS}
            rest = {r: g for r, g in grads.items() if r not in hooked}
            rest_specs = {r: pspecs[r] for r in rest}

            if byzantine is not None and byzantine.num_malicious > 0:
                rest_mal = (jax.lax.axis_index(ax)
                            >= k_agents - byzantine.num_malicious)
            else:
                rest_mal = jnp.asarray(False)

            def agg_rest(g, sp):
                ms = model_only_spec(sp)

                def local(gl, mal):
                    if byzantine is not None and byzantine.num_malicious > 0:
                        gl = attacks_lib.apply_local(
                            gl, mal, byzantine.attack,
                            dict(byzantine.attack_kwargs))
                    return sharded_lib.robust_all_reduce(
                        gl, ax if len(ax) > 1 else ax[0],
                        method=par.aggregation,
                        aggregator="mm_pallas" if par.use_kernel
                        else "mm_tukey",
                        num_iters=par.agg_num_iters)

                return _model_manual(local, (ms, P()), ms)(g, rest_mal)

            rest = jax.tree.map(
                agg_rest, rest, rest_specs,
                is_leaf=lambda x: hasattr(x, "shape"))
            # block leaves that could not be fsdp-hooked (no divisible
            # dim): raw per-agent grads -> same post-hoc aggregation
            gb = grads["blocks"]
            gb_leaves, gb_def = jax.tree.flatten(gb)
            d_leaves = jax.tree.leaves(dims_tree)
            sp_leaves = jax.tree.leaves(
                pspecs["blocks"], is_leaf=lambda x: isinstance(x, P))
            gb_leaves = [
                g if d >= 0 else agg_rest(g, sp)
                for g, d, sp in zip(gb_leaves, d_leaves, sp_leaves)]
            grads["blocks"] = jax.tree.unflatten(gb_def, gb_leaves)
            grads = {**{r: grads[r] for r in hooked}, **rest,
                     "blocks": grads["blocks"]}

            new_params, new_opt = optimizers.update(opt_cfg, params, grads,
                                                    opt_state)
            loss = jax.lax.pmean(jnp.mean(losses), ax)
            gn = optimizers.global_norm(grads)  # local-shard norm (approx)
            return new_params, new_opt, {"loss": loss, "grad_norm": gn}

    opt_template = jax.eval_shape(lambda: optimizers.init(opt_cfg, template))
    ospecs_m = opt_specs(opt_template, mspecs)
    batch_tmpl_spec = None  # provided at lower time via batch arg structure

    def build(batch_template):
        bspecs = batch_specs(batch_template, mesh)
        step = compat.shard_map(
            local_step, mesh=mesh,
            in_specs=(mspecs, ospecs_m, bspecs),
            out_specs=(mspecs, ospecs_m, P()),
            axis_names=set(ax), check_vma=False)
        return step

    return build, pspecs


# ===========================================================================
# serve steps
# ===========================================================================
# Non-FSDP archs: plain GSPMD jit.  FSDP archs: the same manual per-layer
# gather hook as training (fwd only) -- pure GSPMD would hoist the whole
# parameter all-gather out of the layer loop (observed: +13.7 GiB temp and
# a 23 GB all-gather on qwen1.5-110b prefill).

def make_serve_hook(mesh, dims_tree, mspec_tree):
    ax = agent_axes(mesh)

    def hook(blk):
        def one(w, d, ms):
            if d < 0:
                return w

            def gl(wl):
                return jax.lax.all_gather(wl.astype(GATHER_DTYPE), ax,
                                          axis=d, tiled=True)

            return _model_manual(gl, ms, ms)(w)
        return jax.tree.map(one, blk, dims_tree, mspec_tree)

    return hook


def _serve_fsdp_bits(model_cfg, mesh):
    template = jax.eval_shape(
        lambda: M.init_model(jax.random.key(0), model_cfg))
    pspecs = param_specs(template, mesh, fsdp=True)
    k_agents = num_agents(mesh)
    dims_tree = block_dims_tree(template["blocks"], k_agents,
                                mesh.shape.get("model", 1))
    mspec_tree = block_mspec_tree(pspecs["blocks"])
    hook = make_serve_hook(mesh, dims_tree, mspec_tree)
    return pspecs, manual_only(pspecs, mesh), hook


def make_prefill_step(model_cfg: ModelConfig, mesh, *, fsdp: bool = False,
                      batch_template=None):
    if not fsdp:
        def step(params, batch):
            with sharding.use_mesh(mesh):
                return M.prefill(params, model_cfg, batch, remat=False)
        return step

    assert batch_template is not None
    pspecs, mspecs, hook = _serve_fsdp_bits(model_cfg, mesh)
    ax = agent_axes(mesh)
    bspecs = batch_specs(batch_template, mesh)

    def local(params, batch):
        with sharding.use_mesh(mesh, {"batch": (), "fsdp": ()}):
            return M.prefill(params, model_cfg, batch, layer_hook=hook,
                             remat=False)

    out_spec = P(ax if len(ax) > 1 else ax[0])
    return compat.shard_map(local, mesh=mesh, in_specs=(mspecs, bspecs),
                         out_specs=out_spec, axis_names=set(ax),
                         check_vma=False)


def make_decode_step(model_cfg: ModelConfig, mesh, *, fsdp: bool = False,
                     cache_template=None, global_batch: int = 0):
    if not fsdp:
        def step(params, tokens, cache):
            with sharding.use_mesh(mesh):
                logits, cache = M.decode_step(params, model_cfg, tokens,
                                              cache)
                next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return next_tok, cache
        return step

    assert cache_template is not None and global_batch
    pspecs, mspecs, hook = _serve_fsdp_bits(model_cfg, mesh)
    ax = agent_axes(mesh)
    a = ax if len(ax) > 1 else ax[0]
    cspecs = manual_only(
        cache_specs(model_cfg, cache_template, mesh, global_batch), mesh)
    tok_spec = P(a) if global_batch % num_agents(mesh) == 0 else P(None)

    def local(params, tokens, cache):
        with sharding.use_mesh(mesh, {"batch": (), "fsdp": ()}):
            logits, cache = M.decode_step(params, model_cfg, tokens, cache,
                                          layer_hook=hook)
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return next_tok, cache

    return compat.shard_map(local, mesh=mesh,
                         in_specs=(mspecs, tok_spec, cspecs),
                         out_specs=(tok_spec, cspecs), axis_names=set(ax),
                         check_vma=False)
