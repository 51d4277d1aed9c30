"""Pallas TPU kernel for fused elementwise (weighted) MM-aggregation.

The hot loop of the paper's aggregator is, per model coordinate m and
combination weights a (Eq. 10/13; uniform a recovers Eq. 8):

    med   = wmedian_k(phi[k, m]; a)                   (robust init)
    s     = 1.4826 * median_k |phi[k, m] - med|       (MAD scale)
    mu_0  = med
    T x:  b_k = tukey_w((phi[k,m] - mu_t) / (c*s))
          mu_{t+1} = sum a_k b_k phi / sum a_k b_k

A naive jnp composition round-trips HBM ~3+T times (two sorts, T
weighted reductions).  The kernel fuses *everything* into one VMEM
residency per tile and writes the estimate back once.

SINGLE-PASS LAYOUT: the agent axis leads, coordinates fill whole vregs.
Each (K, ...) operand is presented to the kernel as a (K, R, C) view
(``leaf_view``): a leaf whose trailing matrix (A, B) has B % 128 == 0
and A % 8 == 0 is read as its own matrix (R = prod(...) * A, C = B), a
reshape that is a bitcast under the TPU's (8, 128) tiling, so no copy
runs before or after the kernel; anything else is flattened and read as
(M / 128, 128) rows, M padded with ZERO columns (a non-finite pad would
make the in-kernel MAD compute inf - inf = nan).  The agent axis K is
the leading, untiled axis of every block, so agent row ``x[k]`` is a
dense (rows, lanes) slab: the sort network, the median, the MAD and the
Tukey IRLS run on full (8, 128) vregs, and picking an agent's row costs
no relayout.

TPU adaptation notes (vs a GPU port):
  * No `sort` primitive is needed: K is *static*, so the median is a
    bitonic sorting network over the list of agent slabs -- elementwise
    min/max pairs, or a compare and selects when weight planes ride
    along -- pure VPU ops, no data-dependent control flow.  The network
    wants a power-of-two row count; the missing rows are +inf
    sentinels of weight 0 that exist only at trace time, so every
    compare-exchange against one is resolved statically and costs
    nothing.
  * The median/MAD read fixed ranks (K-1)//2 and K//2 of the sorted
    slabs; the weighted median selects the cumulative-weight-0.5
    crossing.  The IRLS multiplies by one reciprocal of c * scale per
    coordinate instead of dividing per agent and sweep.
  * Combination weights are per-agent scalars in SMEM, broadcast onto
    the slabs; each of the N weight columns is estimated in turn from
    the same resident slabs.
  * Compute is float32 internally regardless of input dtype (bf16
    gradients upcast per slab, bf16 written back -- matches the
    reference).

ONE-RESIDENCY BATCHING (grid and streaming).  The launch grid is
(tiles, K_pad // bk): a tile is (bR, bC) coordinates of the view, about
``block_m`` of them, and each (bk, bR, bC) input block is fetched once.
When K streams in several blocks they accumulate in a (K_pad, bR, bC)
f32 VMEM scratch; on the last K step ALL N neighborhood estimates (the
weight columns of a (K, N) combining matrix) are computed from that
single residency, one (8, 128 * j) chunk of slabs at a time.  The N
axis lives in the kernel BODY, not the launch grid, so the number of
HBM fetches of the update matrix is tiles * (K_pad/bk) -- independent
of N.  ``launch_plan`` is the single source of truth for the grid/tile
geometry and the modeled traffic; the benchmarks audit it.

Block sizes default to ``kernels.tuning`` (cached autotuner winner, or
a VMEM-budget heuristic when no measurement is cached).

TWO-PASS K-MAJOR PATH (K >> 64).  The single-pass kernel's working set
is dominated by the full-K sort networks: a chunk holds next_pow2(K)
slabs of values, weight carries and deviations, so large K overflows
VMEM.  ``path="two_pass"`` reads the flattened (K, M) operand in
(bk, bm) blocks on an (M/bm, K/bk) grid with a single input residency,
and replaces the full-K sorts with two passes *over the K axis*:

  pass 1 (every K grid step): the streamed (bk, bm) block is sorted by
      a bk-sized bitonic network (working set scales with bk, not K)
      and per-block robust statistics -- block (weighted) median, block
      MAD, block weight mass -- are emitted into a (K/bk, N, bm) VMEM
      scratch intermediate, tiny relative to the update matrix and
      never round-tripped through HBM (an HBM intermediate would break
      the <= 2x traffic bound: 2 stat planes re-read cost
      4*(K/bk)*N*M bytes against an N*M*itemsize output budget).
  pass 2 (last K step): a mass-weighted median-of-medians/quantile
      init plus a pooled (mass-weighted median) MAD scale, then the
      Tukey IRLS refinement with cross-block accumulation -- the IRLS
      numerator/denominator sums decompose exactly over K blocks, so
      each iteration walks the resident (K_pad, bm) scratch block by
      block with a bounded (bk, n_chunk, bm) working set.  Only the
      init/scale are approximate (exact when K/bk == 1); the refinement
      sums are exact.

The N axis is additionally processed in ``n_chunk`` column chunks so
the transient sort/IRLS planes are (bk, n_chunk, bm) instead of
(K, N, bm).  Like the single-pass plan's, the input blocks are fetched
once (one residency, N-free grid), so total modeled HBM
traffic stays ~1x (bounded by 2x via K padding to bk multiples).
``launch_plan`` models both paths (geometry, traffic, VMEM residency)
and auto-selects: two-pass iff K > 64 and the single-pass VMEM model
exceeds ``VMEM_BUDGET_BYTES``; a ``kernels.tuning`` cached winner (the
measured single<->two-pass crossover) takes precedence.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import location, mestimators

DEFAULT_BLOCK_M = 512
_SCALE_FLOOR = 1e-12
_MAD_CONSISTENCY = 1.4826022185056018

PATHS = ("single", "two_pass")
# the pallas_call's name per path: the Mosaic kernel's name in a profile
KERNEL_NAMES = {"single": "mm_aggregate", "two_pass": "mm_aggregate_two_pass"}
# conservative per-core VMEM budget for the kernel working set (the
# full VMEM is ~16 MB; leave room for double buffering + output).  The
# single source of truth for the heuristic lane tile (kernels.tuning)
# AND the single<->two-pass crossover (``auto_path``).
VMEM_BUDGET_BYTES = 4 * 2 ** 20
# the single-pass path is the measured default for small meshes; the
# two-pass machinery only auto-engages beyond this agent count
_TWO_PASS_MIN_K = 65
# largest K block the two-pass path sorts in one network (bigger K is
# split into multiple blocks -> approximate median-of-medians init)
_MAX_BLOCK_K2 = 512
# transient working-set budget for one (bk, n_chunk, bm) chunk
_CHUNK_BUDGET_BYTES = 2 * 2 ** 20
# trace-size guard: never split N into more than this many chunks
_MAX_N_CHUNKS = 16
# rows per block of the weighted-median prefix scan (see _wquantile_planes)
_SCAN_BLOCK = 16
LANE = 128
# single-pass tiles: at most this many lanes per block row, and one
# compute chunk of at most this many coordinates (two f32 vregs)
_MAX_BLOCK_COLS = 1024
_CHUNK_COORDS = 2048


def sublane_rows(dtype) -> int:
    """Row tile of one vreg for ``dtype``: 8 rows for 32-bit, 16 for
    16-bit.  A block's second-minor extent is a multiple of it (or the
    whole axis), or Mosaic refuses the BlockSpec."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def leaf_view(shape) -> Tuple[str, int, int]:
    """The (R, C) matrix the single-pass kernel reads one agent's
    coordinates as, for a per-agent ``shape``: ``("rows", R, C)`` is the
    leaf's own trailing matrix (B % 128 == 0, A % 8 == 0: the reshape is
    a bitcast), ``("flat", R, 128)`` the flattened coordinates, M padded
    with zeros to R * 128."""
    shape = tuple(int(d) for d in shape)
    if len(shape) >= 2 and shape[-1] % LANE == 0 and shape[-2] % 8 == 0:
        return "rows", math.prod(shape[:-1]), shape[-1]
    return "flat", max(1, -(-math.prod(shape) // LANE)), LANE


def single_pass_tile(rows: int, cols: int, block_m: int,
                     dtype=jnp.float32) -> Tuple[int, int]:
    """(bR, bC) of one single-pass grid step: about ``block_m``
    coordinates of the (R, C) view, at most ``_MAX_BLOCK_COLS`` lanes
    wide, rows a whole number of sublane tiles (or the whole axis when
    it is shorter than one).  Edge tiles may overhang the view: their
    reads are unspecified and their writes dropped, and since every
    coordinate is estimated on its own, the overhang touches nothing."""
    sub = sublane_rows(dtype)
    bc = min(cols, _MAX_BLOCK_COLS,
             max(LANE, (block_m // sub) // LANE * LANE))
    if rows <= sub:
        return rows, bc
    return min(rows // sub * sub,
               max(sub, (block_m // bc) // sub * sub)), bc


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 2, the minimum network size)."""
    p = 2
    while p < n:
        p *= 2
    return p


def _bitonic_stage(x, carries, *, j: int, size: int):
    """One compare-exchange pass of the bitonic network.

    Partners are rows i and i^j; a block of ``size`` rows sorts
    descending iff bit log2(size) of its base index is set (the
    standard iterative bitonic schedule).  All decisions are made on
    ``x``; every array in ``carries`` is swapped with the same mask, so
    carried planes follow the per-column value permutation exactly.
    """
    p = x.shape[0]
    g = p // (2 * j)
    rest = x.shape[1:]
    xr = x.reshape((g, 2, j) + rest)
    x0, x1 = xr[:, 0], xr[:, 1]
    # direction per 2j-block: bit `size` of the block's base row index.
    # Folded to a static bool when uniform over the pass; otherwise an
    # in-kernel iota (pallas kernels cannot capture trace constants).
    desc_np = ((np.arange(g) * 2 * j) & size) != 0
    if not desc_np.any():
        swap = x0 > x1
    elif desc_np.all():
        swap = ~(x0 > x1)
    else:
        gi = jax.lax.broadcasted_iota(
            jnp.int32, (g,) + (1,) * (len(rest) + 1), 0)
        desc = ((gi * (2 * j)) & size) != 0
        swap = (x0 > x1) ^ desc
    x = jnp.stack([jnp.where(swap, x1, x0), jnp.where(swap, x0, x1)],
                  axis=1).reshape((p,) + rest)
    out_carries = []
    for w in carries:
        extra = w.ndim - len(rest) - 1   # axes inserted after the row axis
        ws = swap.reshape(swap.shape[:2] + (1,) * extra + swap.shape[2:])
        wr = w.reshape((g, 2, j) + w.shape[1:])
        w0, w1 = wr[:, 0], wr[:, 1]
        out_carries.append(
            jnp.stack([jnp.where(ws, w1, w0), jnp.where(ws, w0, w1)],
                      axis=1).reshape(w.shape))
    return x, tuple(out_carries)


def _bitonic_sort_rows(x: jnp.ndarray, carries: Tuple[jnp.ndarray, ...] = ()
                       ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, ...]]:
    """Sort along axis 0 (static power-of-two length) by a bitonic
    network, permuting every array in ``carries`` along.

    O(K log^2 K) compare-exchange passes, all static min/max + sublane
    reshapes -- pure VPU work.  ``carries`` may have extra axes between
    the row axis and the trailing lane axes (e.g. (K, N, bm) weight
    planes against (K, bm) values); the swap mask broadcasts across
    them.  Ties keep an arbitrary but x-consistent order: tied values
    are interchangeable, so every consumer (median ranks, cumulative
    weight crossing) is permutation-invariant within a tie group.
    """
    p = x.shape[0]
    assert p >= 2 and p & (p - 1) == 0, "row count must be a power of two"
    size = 2
    while size <= p:
        j = size // 2
        while j >= 1:
            x, carries = _bitonic_stage(x, carries, j=j, size=size)
            j //= 2
        size *= 2
    return x, carries


def _weight_planes(a: jnp.ndarray, bm: int) -> jnp.ndarray:
    """(P, N) weight columns -> (P, N, bm) planes for the paired sort.

    Mosaic cannot reshape a bare broadcast (its lane-replicated layout
    aborts the compiler inside the sort network's row reshapes), so the
    planes are materialized through a select against a lane iota, which
    is always true.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, bm), 2)
    return jnp.where(lane >= 0, a[:, :, None], 0.0)


def _wquantile_planes(xs: jnp.ndarray, ws: jnp.ndarray, half) -> jnp.ndarray:
    """Weighted median crossings of an ascending-sorted tile.

    ws is (P, N, bm) carried weight planes; xs is the matching sorted
    values, (P, bm) (shared across planes) or (P, N, bm).  Per plane,
    select the first value whose cumulative weight reaches ``half``
    (a scalar, or an (N, bm) threshold -- e.g. half the plane's total
    mass).  Sentinel rows carry weight 0 and sort to the end, so they
    are never selected; an all-zero plane selects nothing and returns
    0.  Returns (N, bm).

    Mosaic has no cumsum, so the cumulative weight is a blocked
    sequential scan: rows accumulate left to right inside blocks of
    ``_SCAN_BLOCK`` (all blocks at once), and block b starts exactly
    where block b-1 ended.  Up to ``_SCAN_BLOCK`` rows this is the
    oracle's ``jnp.cumsum`` order; at any size it is monotone in
    floating point, so at most one row crosses.
    """
    vals = xs if xs.ndim == ws.ndim else xs[:, None, :]
    p = ws.shape[0]
    g = min(p, _SCAN_BLOCK)            # p is a power of two, so g | p
    wb = ws.reshape((p // g, g) + ws.shape[1:])
    vb = vals.reshape((p // g, g) + vals.shape[1:])
    local = [wb[:, 0]]                 # in-block running sums
    for i in range(1, g):
        local.append(local[-1] + wb[:, i])
    start = [jnp.zeros(ws.shape[1:], jnp.float32)]
    for b in range(1, p // g):
        start.append(start[-1] + local[-1][b - 1])
    prev = jnp.stack(start)            # (blocks, N, bm)
    start, out = prev, jnp.zeros_like(prev)
    for i in range(g):
        cw = start + local[i]
        out = jnp.where((cw >= half) & (prev < half), vb[:, i], out)
        prev = cw
    # the crossing row lies in one block; the others hold zeros
    return jnp.sum(out, axis=0)


def _rank_median_planes(xs_sorted: jnp.ndarray, cnt) -> jnp.ndarray:
    """Midpoint median of the first ``cnt`` rows of an ascending-sorted
    tile whose pad rows are +inf.  ``cnt`` may be a traced scalar (the
    K block's valid-row count), so the two ranks are selected by mask
    rather than static indexing.  (P, ...) -> (...)."""
    io = jax.lax.broadcasted_iota(jnp.int32, xs_sorted.shape, 0)
    lo = jnp.sum(jnp.where(io == (cnt - 1) // 2, xs_sorted, 0.0), axis=0)
    hi = jnp.sum(jnp.where(io == cnt // 2, xs_sorted, 0.0), axis=0)
    return 0.5 * (lo + hi)


def _sort_slabs(xs, carries=()):
    """Sort a list of agent slabs by a bitonic network, permuting every
    list in ``carries`` along.

    The agent axis is the Python list -- the kernel's leading, untiled
    axis -- so a compare-exchange is an elementwise min/max pair on
    whole slabs, or a compare and selects when planes are carried; no
    reshape.  The list is topped up to a power of two with ``None``, a
    +inf sentinel of weight 0, and every compare-exchange that meets a
    sentinel is resolved here, at trace time.  Returns the sorted list
    (sentinels last) and the carried lists.  Ties keep an arbitrary but
    consistent order: tied values are interchangeable, so the median
    ranks and the cumulative-weight crossing do not depend on it.
    """
    p = next_pow2(len(xs))
    xs = list(xs) + [None] * (p - len(xs))
    carries = [list(cs) + [None] * (p - len(cs)) for cs in carries]
    size = 2
    while size <= p:
        j = size // 2
        while j >= 1:
            for i in range(p):
                partner = i ^ j
                if partner < i:
                    continue
                # blocks of `size` rows alternate ascending / descending
                lo, hi = (partner, i) if i & size else (i, partner)
                a, b = xs[lo], xs[hi]
                if b is None:
                    continue
                if a is None:
                    xs[lo], xs[hi] = b, a
                    for cs in carries:
                        cs[lo], cs[hi] = cs[hi], cs[lo]
                elif not carries:
                    xs[lo], xs[hi] = jnp.minimum(a, b), jnp.maximum(a, b)
                else:
                    swap = a > b
                    xs[lo], xs[hi] = (jnp.where(swap, b, a),
                                      jnp.where(swap, a, b))
                    for cs in carries:
                        cs[lo], cs[hi] = (jnp.where(swap, cs[hi], cs[lo]),
                                          jnp.where(swap, cs[lo], cs[hi]))
            j //= 2
        size *= 2
    return xs, tuple(carries)


def _weighted_median_slabs(xs, ws):
    """The first ascending-sorted value whose cumulative weight reaches
    1/2 (0 where none does); the sentinels at the end are never chosen.
    The running sum adds the rows in order, as the oracle's cumsum."""
    out = cw = 0.0
    for x, w in zip(xs, ws):
        if x is None:
            break
        prev, cw = cw, cw + w
        out = jnp.where((cw >= 0.5) & (prev < 0.5), x, out)
    return out


def _mm_estimate_slabs(xs, a, *, num_iters: int, c: float,
                       unroll: bool = False):
    """MM estimate of one chunk from its K agent slabs (f32).

    ``a`` holds the K combination weights of one neighborhood, scalars
    (normalized), or None for uniform weights, which cancel from the
    IRLS ratio and are not multiplied in.  ``unroll`` unrolls the IRLS
    sweeps, which lets the scheduler overlap them where K alone gives
    it too little independent work.
    """
    k = len(xs)
    if a is None:
        srt, _ = _sort_slabs(xs)
        med = 0.5 * (srt[(k - 1) // 2] + srt[k // 2])
        floor = k * _SCALE_FLOOR       # the oracle's den carries a 1/K
    else:
        planes = [jnp.full(xs[0].shape, w, jnp.float32) for w in a]
        srt, (ws,) = _sort_slabs(xs, (planes,))
        med = _weighted_median_slabs(srt, ws)
        floor = _SCALE_FLOOR
    # MAD is the plain median of |x - med| (matches the oracle)
    ds, _ = _sort_slabs([jnp.abs(x - med) for x in xs])
    scale = jnp.maximum(_MAD_CONSISTENCY * (0.5 * (ds[(k - 1) // 2]
                                                  + ds[k // 2])),
                        _SCALE_FLOOR)
    inv = 1.0 / (c * scale)            # one divide per coordinate

    def body(_, mu):
        num = den = None
        for i, x in enumerate(xs):
            y = (x - mu) * inv
            u = jnp.maximum(1.0 - y * y, 0.0)
            w = u * u if a is None else a[i] * (u * u)    # a_k * b_k
            num = w * x if num is None else num + w * x
            den = w if den is None else den + w
        safe = den > floor
        return jnp.where(safe, num / jnp.where(safe, den, 1.0), mu)

    return jax.lax.fori_loop(0, num_iters, body, med, unroll=unroll)


def _mm_kernel(x_ref, a_ref, o_ref, *residency, k: int, num_iters: int,
               c: float, weighted: bool, chunk: Tuple[int, int],
               unroll: bool):
    """Grid (tiles, K_pad/bk) over (bk, bR, bC) blocks of the (K, R, C)
    view.  When K streams in several blocks they accumulate in the
    (K_pad, bR, bC) VMEM scratch; on the last K step every (sr, cw)
    chunk of the tile is read as K agent slabs and ALL N estimates are
    computed from them (the N axis is a kernel-body loop, not a grid
    axis).  ``a_ref`` holds the (K, N) weights flattened, in SMEM."""
    ki = pl.program_id(1)
    xs_ref = x_ref
    if residency:
        (xs_ref,) = residency
        bk = x_ref.shape[0]
        xs_ref[pl.ds(ki * bk, bk)] = x_ref[...].astype(jnp.float32)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _compute():
        n_out, br, bc = o_ref.shape
        sr, cw = chunk

        def rows_chunk(rows):
            for c0 in range(0, bc, cw):
                cols = pl.ds(c0, cw)
                xs = [xs_ref[i, rows, cols].astype(jnp.float32)
                      for i in range(k)]

                def column(n):
                    a = ([a_ref[i * n_out + n] for i in range(k)]
                         if weighted else None)
                    mu = _mm_estimate_slabs(xs, a, num_iters=num_iters, c=c,
                                            unroll=unroll)
                    o_ref[n, rows, cols] = mu.astype(o_ref.dtype)

                if n_out == 1:
                    column(0)
                else:
                    def each(n, carry):
                        column(n)
                        return carry
                    jax.lax.fori_loop(0, n_out, each, 0)

        if br == sr:
            rows_chunk(pl.ds(0, sr))
        else:
            def body(r, carry):
                rows_chunk(pl.ds(pl.multiple_of(r * sr, sr), sr))
                return carry
            jax.lax.fori_loop(0, br // sr, body, 0)


def _mm_two_pass_kernel(x_ref, a_ref, o_ref, xs_ref, med_ref, mad_ref, *,
                        k: int, block_k: int, n_chunk: int, num_iters: int,
                        c: float, weighted: bool):
    """K-major two-pass kernel (see module docstring).

    Grid (M/bm, K_pad/bk) over the flattened (K, M) operand, with the
    single-pass kernel's one-residency streaming, but the sort networks
    are bk-sized: pass 1
    computes per-K-block robust statistics into the (K/bk, N, bm) VMEM
    scratch intermediates as each block streams in; pass 2 (last K
    step) combines them into a median-of-medians init + pooled MAD
    scale and runs the cross-block-accumulated Tukey IRLS over the
    (K_pad, bm) residency.  The N axis is processed in ``n_chunk``
    column chunks so every transient plane is (bk|KB, n_chunk, bm).
    """
    ki = pl.program_id(1)
    bk = block_k
    kb, n_out, bm = med_ref.shape

    # ---- pass 1: per-block robust statistics (every K grid step) ----
    xb = x_ref[...].astype(jnp.float32)                        # (bk, bm)
    row = jax.lax.broadcasted_iota(jnp.int32, (bk, bm), 0) + ki * bk
    valid = row < k
    cnt = jnp.minimum(k - ki * bk, bk)        # valid rows, >= 1 (ceil grid)
    xs_ref[pl.ds(ki * bk, bk), :] = jnp.where(valid, xb, 0.0)
    xinf = jnp.where(valid, xb, jnp.inf)      # sort operand, pads last
    a_blk = a_ref[pl.ds(ki * bk, bk), :].astype(jnp.float32)   # (bk, N)

    for c0 in range(0, n_out, n_chunk):
        nc = min(n_chunk, n_out - c0)
        if weighted:
            xs, (ws,) = _bitonic_sort_rows(
                xinf, (_weight_planes(a_blk[:, c0:c0 + nc], bm),))
            # block weighted median: crossing at half the BLOCK mass.
            # One block holds all of the (normalized) mass, so the
            # threshold is the oracle's exact 0.5, not a rounded sum.
            half = 0.5 if kb == 1 else 0.5 * jnp.sum(ws, axis=0)
            med_c = _wquantile_planes(xs, ws, half)            # (nc, bm)
        else:
            xs, _ = _bitonic_sort_rows(xinf)
            med_c = _rank_median_planes(xs, cnt)[None]         # (1, bm)
        # block MAD: plain (rank) median of |x - med_n| over the block's
        # valid rows, matching the oracle's unweighted MAD; +inf pads
        # sort to the end and never enter the cnt ranks.
        dev = jnp.abs(xs[:, None, :] - med_c[None]) \
            if weighted else jnp.abs(xs - med_c)[:, None, :]
        ds, _ = _bitonic_sort_rows(dev)
        mad_c = _rank_median_planes(ds, cnt)                   # (nc, bm)
        med_ref[pl.ds(ki, 1), c0:c0 + nc, :] = med_c[None]
        mad_ref[pl.ds(ki, 1), c0:c0 + nc, :] = mad_c[None]

    # ---- pass 2: combine + cross-block IRLS (last K step) ----
    @pl.when(ki == pl.num_programs(1) - 1)
    def _refine():
        a = a_ref[...].astype(jnp.float32)                     # (K_pad, N)
        mass = jnp.sum(a.reshape(kb, bk, n_out), axis=1)       # (KB, N)
        meds = med_ref[...]                                    # (KB, N, bm)
        mads = mad_ref[...]
        kbp = next_pow2(kb)
        if kbp != kb:      # top up the tiny combine sort, in registers
            pad = jnp.full((kbp - kb, n_out, bm), jnp.inf, jnp.float32)
            meds = jnp.concatenate([meds, pad], axis=0)
            mads = jnp.concatenate([mads, pad], axis=0)
            mass = jnp.concatenate(
                [mass, jnp.zeros((kbp - kb, n_out), jnp.float32)], axis=0)
        c2 = jnp.float32(c * c)

        for c0 in range(0, n_out, n_chunk):
            nc = min(n_chunk, n_out - c0)
            mass_c = _weight_planes(mass[:, c0:c0 + nc], bm)
            half = 0.5 * jnp.sum(mass_c, axis=0)               # (nc, bm)
            # init: mass-weighted median of block medians; scale: pooled
            # mass-weighted median of block MADs.  Exact when KB == 1.
            ms, (mw,) = _bitonic_sort_rows(meds[:, c0:c0 + nc, :], (mass_c,))
            mu0 = _wquantile_planes(ms, mw, half)
            ss, (sw,) = _bitonic_sort_rows(mads[:, c0:c0 + nc, :], (mass_c,))
            scale = jnp.maximum(
                _MAD_CONSISTENCY * _wquantile_planes(ss, sw, half),
                _SCALE_FLOOR)

            def body(t, mu, _c0=c0, _scale=scale, _nc=nc):
                # the IRLS num/den sums decompose exactly over K blocks:
                # walk the residency block by block, (bk, nc, bm) live
                def blk(b, acc):
                    num, den = acc
                    rows = pl.ds(pl.multiple_of(b * bk, bk), bk)
                    xb_b = xs_ref[rows, :]             # zeros on pads
                    a_b = a_ref[rows, _c0:_c0 + _nc].astype(jnp.float32)
                    y = (xb_b[:, None, :] - mu[None]) / _scale[None]
                    u = jnp.clip(1.0 - (y * y) / c2, 0.0, 1.0)
                    w = a_b[:, :, None] * (u * u)              # a_k * b_k
                    return (num + jnp.sum(w * xb_b[:, None, :], axis=0),
                            den + jnp.sum(w, axis=0))
                zero = jnp.zeros((_nc, bm), jnp.float32)
                num, den = jax.lax.fori_loop(0, kb, blk, (zero, zero))
                safe = den > _SCALE_FLOOR
                return jnp.where(safe, num / jnp.where(safe, den, 1.0), mu)

            mu = jax.lax.fori_loop(0, num_iters, body, mu0)
            o_ref[c0:c0 + nc, :] = mu.astype(o_ref.dtype)


class LaunchPlan(NamedTuple):
    """Static geometry + modeled HBM traffic of one batched launch.

    Computed by ``launch_plan`` -- the same code path ``_launch`` uses
    to configure the pallas_call -- so benchmarks and tests audit the
    kernel that actually runs, not a parallel model.
    ``input_block_fetches`` counts update-matrix blocks DMA'd from HBM
    -- (bk, bR, bC) blocks of the (K, R, C) view on the single-pass
    path, (bk, bm) blocks of the flattened (K, M) operand on the
    two-pass path, which reads it as one row (R = 1, C = M); it is
    independent of ``n_out`` by construction (the N axis is not a grid
    axis).  ``block_m`` is the resolved knob, about the coordinates per
    grid step; ``block_rows * block_cols`` is the tile that realizes it.
    """
    grid: Tuple[int, int]
    block_m: int
    block_k: int
    k_pad: int
    m_total: int
    n_out: int
    input_block_fetches: int
    input_bytes: int
    weight_bytes: int
    output_bytes: int
    # two-pass extension (defaults describe the single-pass path)
    path: str = "single"
    n_chunk: int = 1
    num_k_blocks: int = 1
    stats_bytes: int = 0      # VMEM-resident per-block stat intermediate
    vmem_bytes: int = 0       # modeled peak VMEM working set
    # the (R, C) view of one agent's coordinates and the tile of a step
    view: str = "flat"        # "rows": the leaf's own matrix; "flat"
    rows: int = 1
    cols: int = 0
    block_rows: int = 1
    block_cols: int = 0

    @property
    def total_bytes(self) -> int:
        """Total modeled HBM traffic of one launch.  Both paths stream
        the update matrix exactly once (the two-pass intermediate lives
        in VMEM scratch, never HBM)."""
        return self.input_bytes + self.weight_bytes + self.output_bytes

    def tile(self, t: int) -> Tuple[int, ...]:
        """Block index of grid tile ``t`` in the view, after the agent
        axis: (row block, column block), or (column block,) when the
        view is one row (the two-pass (K, M) operand)."""
        if self.path == "two_pass":
            return (t,)
        return divmod(t, -(-self.cols // self.block_cols))

    @property
    def residency_bytes(self) -> int:
        """The f32 (K_pad, tile) VMEM scratch the K blocks accumulate in
        -- always on the two-pass path, only when K streams in several
        blocks on the single-pass path (one block is its own residency)."""
        if self.path == "single" and self.num_k_blocks == 1:
            return 0
        return 4 * self.k_pad * self.block_rows * self.block_cols


def single_pass_vmem_bytes(k: int, n: int, block_m: int, *,
                           block_k: Optional[int] = None,
                           itemsize: int = 4) -> int:
    """Modeled peak VMEM working set of the single-pass kernel at
    ``block_m`` coordinates per grid step: the double-buffered (bk,
    tile) input and (N, tile) output blocks, the f32 (K_pad, tile)
    residency when K streams in several blocks, and one compute chunk
    (at most ``_CHUNK_COORDS`` coordinates): the K agent slabs plus ~8
    next_pow2(K)-row planes (weight carries and their sort ping-pong,
    deviations and their sorted copy, IRLS temporaries).  The N weight
    columns are estimated one after another, so N enters only through
    the output blocks."""
    bk = k + (k % 2) if block_k is None else int(block_k)
    k_pad = -(-k // bk) * bk
    p = next_pow2(max(k, 2))
    residency = 4 * k_pad * block_m if k_pad > bk else 0
    return (2 * (bk + n) * block_m * itemsize + residency
            + 4 * (k_pad + 8 * p) * min(block_m, _CHUNK_COORDS))


def two_pass_vmem_bytes(k: int, n: int, block_m: int, block_k: int,
                        n_chunk: int) -> int:
    """Modeled peak VMEM working set of the two-pass kernel: the
    (K_pad, bm) residency, the (KB, N, bm) x2 stat intermediates, and
    the largest transient phase -- bk-sized pass-1 sorts, the KB-sized
    combine sort, or the (bk, n_chunk, bm) IRLS block -- all bounded by
    (bk | KB, n_chunk, bm), never (K, N, bm)."""
    kb = -(-k // block_k)
    k_pad = kb * block_k
    kbp = next_pow2(max(kb, 2))
    stats = 2 * kb * n * block_m * 4
    sort_p1 = 4 * (2 * block_k * block_m + 3 * block_k * n_chunk * block_m)
    combine = 4 * 3 * kbp * n_chunk * block_m
    irls = 4 * 3 * block_k * n_chunk * block_m
    return 4 * k_pad * block_m + stats + max(sort_p1, combine, irls)


def two_pass_block_k(k: int) -> int:
    """Default K block for the two-pass path: one power-of-two block
    covering the whole axis while it fits a sort network (<= 512 rows,
    KB == 1 -> exact init), else the largest network the budget allows
    (KB > 1 -> median-of-medians init)."""
    return min(next_pow2(max(int(k), 2)), _MAX_BLOCK_K2)


def two_pass_n_chunk(n: int, block_m: int, block_k: int) -> int:
    """Largest N chunk whose transient planes fit the chunk budget,
    floored so the static chunk loop never exceeds _MAX_N_CHUNKS
    (trace-size guard; the VMEM model reports the honest cost)."""
    nc = max(1, _CHUNK_BUDGET_BYTES // (16 * block_k * block_m))
    nc = min(int(n), nc)
    while -(-n // nc) > _MAX_N_CHUNKS:
        nc *= 2
    return min(int(n), nc)


def auto_path(k: int, n: int, block_m: int) -> str:
    """The heuristic single<->two-pass crossover (used when no autotuned
    winner is cached): two-pass iff the mesh is larger than the
    single-pass sweet spot AND the single-pass VMEM model overflows the
    budget.  Small meshes always stay on the measured single-pass path
    (bit-stable with the pre-two-pass kernel)."""
    if int(k) >= _TWO_PASS_MIN_K and \
            single_pass_vmem_bytes(k, n, block_m) > VMEM_BUDGET_BYTES:
        return "two_pass"
    return "single"


def launch_plan(k: int, m: int, n: int = 1, *,
                dtype=jnp.float32,
                block_m: Optional[int] = None,
                block_k: Optional[int] = None,
                path: Optional[str] = None,
                n_chunk: Optional[int] = None,
                shape: Optional[Tuple[int, ...]] = None) -> LaunchPlan:
    """Resolve the kernel path + tile sizes (via kernels.tuning when
    unset) and derive the grid, modeled HBM traffic and modeled VMEM
    residency for a (K, M) x (K, N) run.  ``shape`` is one agent's
    coordinate shape (M of them; default ``(m,)``): it picks the
    single-pass view (``leaf_view``).  ``path=None`` auto-selects: the
    cached tuning winner for the workload if one names a path, else the
    ``auto_path`` VMEM-crossover heuristic."""
    if path is not None and path not in PATHS:
        raise ValueError(f"unknown kernel path {path!r}; known: {PATHS}")
    if block_m is None or block_k is None or path is None:
        from repro.kernels import tuning  # deferred: tuning times _launch
        choice = tuning.get_choice(k, m, n=n, dtype=dtype)
        if block_m is None:
            block_m = choice.block_m
        if path is None:
            path = choice.path
        if block_k is None and (choice.path or "single") == \
                (path or auto_path(k, n, block_m)):
            # a cached block_k only transfers to the path it was
            # measured on (a single-pass bk is not a valid 2-pass bk)
            block_k = choice.block_k
    if path is None:
        path = auto_path(k, n, block_m)

    itemsize = jnp.dtype(dtype).itemsize

    if path == "two_pass":
        m_total = m + ((-m) % block_m)
        bk = two_pass_block_k(k) if block_k is None else int(block_k)
        if bk < 2 or bk & (bk - 1):
            raise ValueError(
                f"two-pass block_k must be a power of two >= 2, got {bk}")
        kb = -(-k // bk)
        k_pad = kb * bk
        nc = two_pass_n_chunk(n, block_m, bk) if n_chunk is None \
            else max(1, min(int(n_chunk), n))
        grid = (m_total // block_m, kb)
        fetches = grid[0] * grid[1]
        return LaunchPlan(
            grid=grid, block_m=block_m, block_k=bk, k_pad=k_pad,
            m_total=m_total, n_out=n,
            input_block_fetches=fetches,
            input_bytes=fetches * bk * block_m * itemsize,
            weight_bytes=k_pad * n * 4,
            output_bytes=n * m_total * itemsize,
            path=path, n_chunk=nc, num_k_blocks=kb,
            stats_bytes=2 * kb * n * block_m * 4,
            vmem_bytes=two_pass_vmem_bytes(k, n, block_m, bk, nc),
            rows=1, cols=m_total, block_rows=1, block_cols=block_m,
        )

    if block_k is None:
        bk = k + (k % 2)
    else:
        if block_k % 2 != 0 or block_k <= 0:
            raise ValueError(f"block_k must be positive and even, got {block_k}")
        bk = block_k
    k_pad = ((k + bk - 1) // bk) * bk
    view, rows, cols = leaf_view((m,) if shape is None else shape)
    br, bc = single_pass_tile(rows, cols, block_m, dtype)
    grid = (-(-rows // br) * -(-cols // bc), k_pad // bk)
    fetches = grid[0] * grid[1]
    return LaunchPlan(
        grid=grid, block_m=block_m, block_k=bk, k_pad=k_pad,
        m_total=rows * cols, n_out=n,
        input_block_fetches=fetches,
        input_bytes=fetches * bk * br * bc * itemsize,
        weight_bytes=k * n * 4,
        output_bytes=n * rows * cols * itemsize,
        path=path, n_chunk=1, num_k_blocks=k_pad // bk,
        stats_bytes=0,
        vmem_bytes=single_pass_vmem_bytes(k, n, br * bc, block_k=bk,
                                          itemsize=itemsize),
        view=view, rows=rows, cols=cols, block_rows=br, block_cols=bc,
    )


def plan_of_record(r: dict) -> LaunchPlan:
    """The launch plan of one ``ops.record_workloads()`` entry."""
    shape = (r["rows"], r["cols"]) if r.get("view") == "rows" else None
    return launch_plan(r["k"], r["m"], r["n"], dtype=r["dtype"],
                       block_m=r["block_m"], block_k=r["block_k"],
                       path=r.get("path"), shape=shape)


class KernelCall(NamedTuple):
    """The realized ``pallas_call`` configuration of one launch.

    Built by ``kernel_call`` from a ``LaunchPlan`` -- the SAME code path
    ``_launch`` uses to configure the pallas_call -- so the static
    contract checker (``repro.analysis.contracts``) audits the kernel
    that actually runs: BlockSpec index maps (one-residency / traffic),
    scratch shapes (VMEM model), and the HBM output surface (two-pass
    stats must never be an output).
    """
    kernel: object                  # the partial'd kernel body
    grid: Tuple[int, int]
    in_specs: Tuple[pl.BlockSpec, ...]   # (x values, a weight columns)
    out_specs: pl.BlockSpec
    out_shape: jax.ShapeDtypeStruct
    scratch_shapes: Tuple[object, ...]   # pltpu.VMEM declarations

    def scratch_bytes(self) -> int:
        """Total bytes of the declared VMEM scratch buffers."""
        total = 0
        for s in self.scratch_shapes:
            n = 1
            for d in s.shape:
                n *= int(d)
            total += n * jnp.dtype(s.dtype).itemsize
        return total


def kernel_call(plan: LaunchPlan, *, k: int, dtype=jnp.float32,
                num_iters: int = 10, c: float = mestimators.TUKEY_C95,
                weighted: bool = True) -> KernelCall:
    """Build the exact pallas_call configuration for ``plan``.

    ``_launch`` runs precisely this configuration; exposing it as data
    lets ``repro.analysis.contracts`` statically verify the launch plan
    against the realized kernel without executing anything.
    """
    bk, k_pad, n_out = plan.block_k, plan.k_pad, plan.n_out
    if plan.path == "two_pass":
        kernel = functools.partial(
            _mm_two_pass_kernel, k=k, block_k=bk, n_chunk=plan.n_chunk,
            num_iters=num_iters, c=c, weighted=weighted)
        scratch = (
            pltpu.VMEM((k_pad, plan.block_m), jnp.float32),
            pltpu.VMEM((plan.num_k_blocks, n_out, plan.block_m),
                       jnp.float32),
            pltpu.VMEM((plan.num_k_blocks, n_out, plan.block_m),
                       jnp.float32),
        )
        return KernelCall(
            kernel=kernel,
            grid=plan.grid,
            in_specs=(
                pl.BlockSpec((bk, plan.block_m), lambda mi, ki: (ki, mi)),
                pl.BlockSpec((k_pad, n_out), lambda mi, ki: (0, 0)),
            ),
            out_specs=pl.BlockSpec((n_out, plan.block_m),
                                   lambda mi, ki: (0, mi)),
            out_shape=jax.ShapeDtypeStruct((n_out, plan.m_total), dtype),
            scratch_shapes=scratch,
        )

    br, bc = plan.block_rows, plan.block_cols
    # one compute chunk: a sublane tile of rows.  Up to 8 agents the
    # slabs stay in registers at two vregs each; up to 16 the IRLS
    # sweeps are unrolled -- both give the scheduler independent work
    # (on one v5e chip the unrolled sweeps ran 1.45x, 1.21x and 1.44x
    # as fast at K=4, 8 and 16, two-vreg slabs 1.9x and 1.06x at K=4, 8)
    sr = min(sublane_rows(dtype), br)
    cw = 2 * LANE if (k <= 8 and bc % (2 * LANE) == 0
                      and sr * 2 * LANE <= _CHUNK_COORDS) else LANE
    kernel = functools.partial(_mm_kernel, k=k, num_iters=num_iters, c=c,
                               weighted=weighted, chunk=(sr, cw),
                               unroll=k <= 16)
    scratch = ((pltpu.VMEM((k_pad, br, bc), jnp.float32),)
               if plan.num_k_blocks > 1 else ())
    tile = plan.tile
    return KernelCall(
        kernel=kernel,
        grid=plan.grid,
        in_specs=(
            pl.BlockSpec((bk, br, bc), lambda t, ki: (ki,) + tile(t)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        out_specs=pl.BlockSpec((n_out, br, bc), lambda t, ki: (0,) + tile(t)),
        out_shape=jax.ShapeDtypeStruct((n_out, plan.rows, plan.cols), dtype),
        scratch_shapes=scratch,
    )


def _pad_inputs(
    x: jnp.ndarray, a: jnp.ndarray, *, plan: LaunchPlan
) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Pad (K, M) values and (K, N) weights to the two-pass plan's grid.

    K is padded to a multiple of the (even) K block with +inf sentinel
    rows (weight 0); the kernel tops the sort operand up to the next
    power of two in registers.  M is padded to a block multiple with
    ZERO columns: a non-finite M pad would flow through the in-kernel
    MAD as inf - inf = nan (the pre-fix behavior); zero columns are
    inert (median 0, scale floored, IRLS exact).
    """
    k, m = x.shape
    bk, k_pad = plan.block_k, plan.k_pad
    m_pad = plan.m_total - m

    xp = x
    if k_pad != k:
        xp = jnp.concatenate(
            [xp, jnp.full((k_pad - k, m), jnp.inf, dtype=x.dtype)], axis=0)
    if m_pad:
        xp = jnp.concatenate(
            [xp, jnp.zeros((k_pad, m_pad), dtype=x.dtype)], axis=1)
    ap = a.astype(jnp.float32)
    if k_pad != k:
        ap = jnp.concatenate(
            [ap, jnp.zeros((k_pad - k, ap.shape[1]), jnp.float32)], axis=0)
    return xp, ap, bk


def _view_inputs(x: jnp.ndarray, plan: LaunchPlan) -> jnp.ndarray:
    """(K, ...) values -> the single-pass plan's (K_pad, R, C) view.

    The "rows" view is a reshape of the leaf (a bitcast on the TPU).
    The "flat" view pads M with ZERO columns to R * 128: a non-finite
    pad would flow through the in-kernel MAD as inf - inf = nan; zero
    columns are inert (median 0, scale floored, IRLS exact).  K pad rows
    (a K block that does not divide K) are zeros the kernel never reads.
    """
    k = x.shape[0]
    if plan.view == "rows":
        xv = x.reshape(k, plan.rows, plan.cols)
    else:
        flat = x.reshape(k, -1)
        xv = jnp.pad(flat, ((0, 0), (0, plan.m_total - flat.shape[1])))
        xv = xv.reshape(k, plan.rows, plan.cols)
    if plan.k_pad != k:
        xv = jnp.pad(xv, ((0, plan.k_pad - k), (0, 0), (0, 0)))
    return xv


def _launch(
    x: jnp.ndarray,
    a: jnp.ndarray,                  # (K, N) weight columns
    *,
    weighted: bool,
    num_iters: int,
    c: float,
    block_m: Optional[int],
    block_k: Optional[int],
    interpret: Optional[bool],
    path: Optional[str] = None,
    n_chunk: Optional[int] = None,
) -> jnp.ndarray:
    """Run the fused kernel: (K, ...) values x (K, N) weights -> (N, ...).

    Weight columns are normalized (and invalid columns replaced by
    uniform) here -- the in-kernel weighted median selects the absolute
    cumulative-weight-0.5 crossing, so unnormalized weights would be
    silently wrong, not just scaled.  ``path`` picks the single-pass or
    two-pass kernel (None = launch_plan's auto selection).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    k, shape = x.shape[0], tuple(x.shape[1:])
    m = math.prod(shape)
    if weighted:
        a = location.normalize_weights(a, dtype=jnp.float32)
    n_out = a.shape[1]
    plan = launch_plan(k, m, n_out, dtype=x.dtype,
                       block_m=block_m, block_k=block_k,
                       path=path, n_chunk=n_chunk, shape=shape)
    if plan.path == "two_pass":
        xp, ap, _ = _pad_inputs(x.reshape(k, m), a, plan=plan)
    else:
        xp, ap = _view_inputs(x, plan), a.astype(jnp.float32).reshape(-1)
    call = kernel_call(plan, k=k, dtype=x.dtype, num_iters=num_iters, c=c,
                       weighted=weighted)
    out = pl.pallas_call(
        call.kernel,
        grid=call.grid,
        in_specs=list(call.in_specs),
        out_specs=call.out_specs,
        out_shape=call.out_shape,
        scratch_shapes=list(call.scratch_shapes),
        interpret=interpret,
        name=KERNEL_NAMES[plan.path],
    )(xp, ap)
    if plan.view == "rows":
        return out.reshape((n_out,) + shape)
    return out.reshape(n_out, -1)[:, :m].reshape((n_out,) + shape)


def _uniform_weights(k: int) -> jnp.ndarray:
    return jnp.full((k, 1), 1.0 / k, dtype=jnp.float32)


def mm_aggregate_nd(
    x: jnp.ndarray,
    a: Optional[jnp.ndarray] = None,
    *,
    num_iters: int = 10,
    c: float = mestimators.TUKEY_C95,
    block_m: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    path: Optional[str] = None,
    n_chunk: Optional[int] = None,
) -> jnp.ndarray:
    """MM-aggregate a (K, ...) array along axis 0 via Pallas, one launch.

    ``a`` is None (uniform), a (K,) vector of combination weights
    (-> (...)), or (K, N) weight columns, one neighborhood each (a_{.n}
    of Eq. 15; -> (N, ...)); weights are normalized internally (invalid
    columns fall back to uniform, as in
    ``repro.core.location.normalize_weights``).  The trailing shape
    picks the single-pass view (``leaf_view``), so a leaf is read in its
    own layout where it can be.  Block sizes and the kernel path default
    to the kernels.tuning cache/heuristic.
    """
    k = x.shape[0]
    batched = a is not None and a.ndim == 2
    if a is None:
        aw = _uniform_weights(k)
    elif a.shape == (k,) or (batched and a.shape[0] == k):
        aw = a.reshape(k, -1)
    else:
        raise ValueError(f"weights must be ({k},) or ({k}, N), got {a.shape}")
    out = _launch(x, aw, weighted=a is not None, num_iters=num_iters, c=c,
                  block_m=block_m, block_k=block_k, interpret=interpret,
                  path=path, n_chunk=n_chunk)
    return out if batched else out[0]


def mm_aggregate_2d(
    x: jnp.ndarray,
    a: Optional[jnp.ndarray] = None,
    *,
    num_iters: int = 10,
    c: float = mestimators.TUKEY_C95,
    block_m: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    path: Optional[str] = None,
) -> jnp.ndarray:
    """MM-aggregate a (K, M) array along axis 0 -> (M,) via Pallas, with
    optional (K,) combination weights (see ``mm_aggregate_nd``)."""
    if x.ndim != 2:
        raise ValueError(f"mm_aggregate_2d wants (K, M), got {x.shape}")
    if a is not None and a.shape != (x.shape[0],):
        raise ValueError(f"weights must be ({x.shape[0]},), got {a.shape}")
    return mm_aggregate_nd(x, a, num_iters=num_iters, c=c, block_m=block_m,
                           block_k=block_k, interpret=interpret, path=path)


def mm_aggregate_batched_2d(
    x: jnp.ndarray,
    a: jnp.ndarray,
    *,
    num_iters: int = 10,
    c: float = mestimators.TUKEY_C95,
    block_m: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    path: Optional[str] = None,
    n_chunk: Optional[int] = None,
) -> jnp.ndarray:
    """Batched weighted MM-aggregation: (K, M) values, (K, N) weight
    columns -> (N, M) estimates, one kernel launch.

    The x tile is streamed from HBM exactly ONCE regardless of N -- all
    N estimates are computed in the kernel body from the single VMEM
    residency (see the module docstring); this is the diffusion hot
    path (K, N = graph size).  ``path`` selects the single-pass or
    two-pass (K >> 64) kernel; None auto-selects via launch_plan.
    """
    if x.ndim != 2 or a.ndim != 2 or a.shape[0] != x.shape[0]:
        raise ValueError(
            f"want x (K, M) and a (K, N), got {x.shape} and {a.shape}")
    return mm_aggregate_nd(x, a, num_iters=num_iters, c=c, block_m=block_m,
                           block_k=block_k, interpret=interpret, path=path,
                           n_chunk=n_chunk)
