"""Static kernel-contract checker.

Verifies ``mm_aggregate.launch_plan`` against the *realized* kernel
configuration (``mm_aggregate.kernel_call`` -- the same object
``_launch`` hands to ``pl.pallas_call``) for both kernel paths, without
executing anything:

  one-residency   the input BlockSpec index map, enumerated over the
                  whole grid, fetches each (K block, tile) update block
                  exactly once -- (bk, bR, bC) blocks of the single-pass
                  (K, R, C) view, (bk, bm) blocks of the two-pass (K, M)
                  operand -- the fetch count equals the plan's modeled
                  ``input_block_fetches``, and re-deriving the plan at a
                  different N leaves the input traffic unchanged
                  (N-independence -- the N axis must never re-enter the
                  grid).
  injectivity     the grid -> input-tile map is injective (no program
                  re-fetches another program's tile); the output map
                  writes each (N, bm) tile from the M grid axis only
                  (K steps revisit the same tile -- the accumulation
                  pattern -- but never two different tiles); the
                  weights are one residency (a whole-array SMEM operand,
                  or the single zero block).
  vmem-model      the declared VMEM scratch buffers match the modeled
                  working set: the residency + two-pass stat buffers
                  are exactly the modeled terms, the total is within
                  ``single_pass_vmem_bytes``/``two_pass_vmem_bytes``,
                  and the model is within ``VMEM_BUDGET_BYTES``.
  hbm-surface     the launch has exactly ONE HBM output, the (N, M)
                  estimate -- two-pass per-K-block stats live only in
                  VMEM scratch (an HBM stat round-trip would break the
                  <= 2x traffic bound, see the kernel's module
                  docstring).

``check_workloads`` audits a representative workload matrix (both
paths, f32 + bf16, auto-resolved and pinned geometry); the mutation
tests feed deliberately broken configurations through ``audit_call`` to
prove each rule has teeth.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from repro.analysis.findings import Finding
from repro.kernels import mm_aggregate as mk

# (k, m, n, dtype, path): both paths, weighted batching, bf16 streams,
# and the auto-resolved path for a large-K mesh (whatever the tuning
# cache says, the structural contracts must hold for the result).
DEFAULT_WORKLOADS: Tuple[tuple, ...] = (
    (8, 1000, 1, "float32", None),
    (16, 512, 16, "float32", "single"),
    (16, 300, 4, "bfloat16", None),
    (33, 700, 5, "float32", None),
    (128, 512, 4, "float32", "two_pass"),
    (1024, 2048, 1, "float32", None),
    (1024, 600, 8, "bfloat16", "two_pass"),
)


def _grid_points(grid: Sequence[int]):
    mi_n, ki_n = grid
    for mi in range(mi_n):
        for ki in range(ki_n):
            yield mi, ki


def _where(plan: mk.LaunchPlan) -> str:
    return (f"K{plan.k_pad}xM{plan.m_total}xN{plan.n_out}"
            f"/{plan.path}/bm{plan.block_m}_bk{plan.block_k}")


def _blocks(plan: mk.LaunchPlan) -> Tuple[tuple, tuple, tuple]:
    """The planned (input block, output block, output array) shapes."""
    if plan.path == "two_pass":
        return ((plan.block_k, plan.block_m), (plan.n_out, plan.block_m),
                (plan.n_out, plan.m_total))
    tile = (plan.block_rows, plan.block_cols)
    return ((plan.block_k,) + tile, (plan.n_out,) + tile,
            (plan.n_out, plan.rows, plan.cols))


def _plan_shape(plan: mk.LaunchPlan) -> Optional[tuple]:
    """A per-agent shape that re-derives the plan's view."""
    return (plan.rows, plan.cols) if plan.view == "rows" else None


def audit_call(plan: mk.LaunchPlan, call: mk.KernelCall,
               *, dtype="float32") -> List[Finding]:
    """Audit one realized kernel configuration against its plan."""
    out: List[Finding] = []
    where = _where(plan)

    def finding(rule: str, detail: str, ident: str = "") -> None:
        out.append(Finding(rule=rule, path="kernel", where=where,
                           detail=detail, ident=ident))

    # --- grid geometry must be the plan's, verbatim ---
    if tuple(call.grid) != tuple(plan.grid):
        finding("grid-mismatch",
                f"realized grid {tuple(call.grid)} != planned "
                f"{tuple(plan.grid)}")
        return out  # everything below keys off the grid

    # --- one-residency: each input tile fetched exactly once ---
    x_spec = call.in_specs[0]
    fetches = [tuple(x_spec.index_map(mi, ki))
               for mi, ki in _grid_points(call.grid)]
    n_fetches = len(fetches)
    distinct = set(fetches)
    if n_fetches != plan.input_block_fetches:
        finding("one-residency",
                f"index map fetches {n_fetches} input blocks over the "
                f"grid; plan models {plan.input_block_fetches}")
    if len(distinct) != n_fetches:
        dup = n_fetches - len(distinct)
        finding("one-residency",
                f"{dup} input-tile re-fetches: the grid -> input-tile "
                "map is not injective, so some update block is streamed "
                "from HBM more than once per launch", ident="refetch")
    expected_tiles = {(ki,) + plan.tile(mi)
                      for mi, ki in _grid_points(call.grid)}
    if distinct != expected_tiles:
        finding("one-residency",
                "input index map does not cover each (K block, M block) "
                "tile exactly once (missing or out-of-range tiles)",
                ident="coverage")
    x_block, o_block, out_array = _blocks(plan)
    if tuple(x_spec.block_shape) != x_block:
        finding("one-residency",
                f"input block shape {tuple(x_spec.block_shape)} != "
                f"planned {x_block}",
                ident="block-shape")

    # --- N-independence: input traffic must not scale with N ---
    alt_n = plan.n_out * 4 + 1
    alt = mk.launch_plan(plan.k_pad, plan.m_total, alt_n, dtype=dtype,
                         block_m=plan.block_m,
                         block_k=plan.block_k, path=plan.path,
                         shape=_plan_shape(plan))
    if alt.input_block_fetches != plan.input_block_fetches or \
            alt.grid != plan.grid:
        finding("n-independence",
                f"input traffic changes with N: N={plan.n_out} fetches "
                f"{plan.input_block_fetches} blocks on grid {plan.grid}, "
                f"N={alt_n} fetches {alt.input_block_fetches} on "
                f"{alt.grid} -- the N axis re-entered the launch grid")

    # --- weight operand: one broadcast residency, not per-step slices ---
    a_spec = call.in_specs[1]
    if a_spec.index_map is not None:      # None: the whole array, once
        a_tiles = {tuple(a_spec.index_map(mi, ki))
                   for mi, ki in _grid_points(call.grid)}
        if len(a_tiles) != 1 or any(next(iter(a_tiles))):
            finding("one-residency",
                    f"weight index map addresses tiles {sorted(a_tiles)};"
                    " expected the single broadcast zero-block residency",
                    ident="weights")

    # --- output surface: one (N, tile) block per M index, M-axis only ---
    o_tiles = {}
    for mi, ki in _grid_points(call.grid):
        o_tiles.setdefault(mi, set()).add(tuple(call.out_specs.index_map(mi, ki)))
    for mi, tiles in o_tiles.items():
        if len(tiles) != 1:
            finding("output-map",
                    f"M grid index {mi} writes {len(tiles)} different "
                    "output tiles; the K axis must revisit one tile")
            break
    written = {t for tiles in o_tiles.values() for t in tiles}
    if len(written) != call.grid[0]:
        finding("output-map",
                f"{len(written)} distinct output tiles written by "
                f"{call.grid[0]} M blocks; the M -> output-tile map "
                "must be injective")

    # --- HBM surface: exactly one output, and never the stat planes ---
    shapes = call.out_shape if isinstance(call.out_shape, (list, tuple)) \
        else [call.out_shape]
    if len(shapes) != 1:
        finding("hbm-stats",
                f"kernel declares {len(shapes)} HBM outputs; the only "
                "HBM write is the (N, M) estimate -- per-K-block stats "
                "must stay in VMEM scratch or the <= 2x traffic bound "
                "breaks")
    expected_out = out_array
    stats_shape = (plan.num_k_blocks, plan.n_out, plan.block_m)
    for s in shapes:
        if tuple(s.shape) == stats_shape and plan.path == "two_pass":
            finding("hbm-stats",
                    f"a {stats_shape} per-K-block stat buffer is an HBM "
                    "output; stats must live only in VMEM scratch",
                    ident="stats-output")
        elif tuple(s.shape) != expected_out:
            finding("hbm-stats",
                    f"unexpected HBM output shape {tuple(s.shape)}; "
                    f"the estimate is {expected_out}", ident="extra-output")

    if tuple(call.out_specs.block_shape) != o_block:
        finding("output-map",
                f"output block shape {tuple(call.out_specs.block_shape)} "
                f"!= planned {o_block}")

    # --- VMEM model: declared scratch must match the modeled terms ---
    residency = plan.residency_bytes
    expected_scratch = residency + plan.stats_bytes
    declared = call.scratch_bytes()
    if declared != expected_scratch:
        finding("vmem-model",
                f"declared VMEM scratch is {declared} bytes; the plan "
                f"models residency {residency} + stats "
                f"{plan.stats_bytes} = {expected_scratch}")
    if plan.path == "two_pass":
        model = mk.two_pass_vmem_bytes(plan.k_pad, plan.n_out, plan.block_m,
                                       plan.block_k, plan.n_chunk)
    else:
        model = mk.single_pass_vmem_bytes(
            plan.k_pad, plan.n_out, plan.block_rows * plan.block_cols,
            block_k=plan.block_k, itemsize=jnp.dtype(dtype).itemsize)
    if plan.vmem_bytes != model:
        finding("vmem-model",
                f"plan.vmem_bytes {plan.vmem_bytes} != the "
                f"{plan.path}-path model {model} at the plan's geometry",
                ident="plan-model")
    if declared > plan.vmem_bytes:
        finding("vmem-model",
                f"declared scratch ({declared} bytes) exceeds the "
                f"modeled peak working set ({plan.vmem_bytes})",
                ident="scratch-over-model")
    if plan.vmem_bytes > mk.VMEM_BUDGET_BYTES:
        # the one sanctioned overflow: a mesh below the two-pass
        # crossover whose single-pass model overflows even at the
        # narrowest tile -- the engine keeps those single-pass for
        # bit-stability with the pre-two-pass kernel.  Anything else
        # means the resolver left budget on the table (a narrower tile
        # or the two-pass path would have fit).
        narrow = mk.single_pass_vmem_bytes(
            plan.k_pad, plan.n_out, mk.LANE, block_k=plan.block_k,
            itemsize=jnp.dtype(dtype).itemsize)
        forced_small_mesh = (plan.path == "single"
                             and plan.k_pad < mk._TWO_PASS_MIN_K
                             and narrow > mk.VMEM_BUDGET_BYTES)
        if not forced_small_mesh:
            finding("vmem-budget",
                    f"modeled working set {plan.vmem_bytes} bytes "
                    f"exceeds VMEM_BUDGET_BYTES ({mk.VMEM_BUDGET_BYTES})"
                    " and the geometry was avoidable: a narrower tile "
                    "or the two-pass path fits the budget")
    return out


def check_workload(k: int, m: int, n: int, dtype="float32",
                   path: Optional[str] = None, *,
                   block_m: Optional[int] = None,
                   block_k: Optional[int] = None) -> List[Finding]:
    """Plan + realize one workload and audit the pair."""
    dt = jnp.dtype(dtype)
    plan = mk.launch_plan(k, m, n, dtype=dt, block_m=block_m,
                          block_k=block_k, path=path)
    call = mk.kernel_call(plan, k=k, dtype=dt)
    findings = audit_call(plan, call, dtype=dt)
    # auto-resolution sanity: when the caller pinned nothing, the
    # resolved path must agree with the plan's own crossover rule
    # whenever no tuning-cache winner overrides it.
    if path is None and block_m is None and block_k is None:
        from repro.kernels import tuning
        choice = tuning.get_choice(k, m, n=n, dtype=dt)
        if choice.path is None:
            want = mk.auto_path(k, n, plan.block_m)
            if plan.path != want:
                findings.append(Finding(
                    rule="path-crossover", path="kernel",
                    where=_where(plan),
                    detail=f"auto-resolved path {plan.path!r} disagrees "
                           f"with the VMEM crossover heuristic {want!r} "
                           "(and no tuning winner pins it)"))
    return findings


def check_workloads(workloads: Iterable[tuple] = DEFAULT_WORKLOADS,
                    ) -> List[Finding]:
    """The contracts pass: audit every workload in the matrix."""
    out: List[Finding] = []
    for wl in workloads:
        out.extend(check_workload(*wl))
    return out
