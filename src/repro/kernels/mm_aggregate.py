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
residency per (K, bm) tile: the agent axis K is small (the mesh's data
axis, <= 64 here), so a full tile of K rows x bm lanes sits in a few
hundred KB of VMEM, and the whole estimate is computed before the tile
is written back once.

TPU adaptation notes (vs a GPU port):
  * No `sort` primitive is needed: K is *static*, so the median is a
    bitonic sorting network (O(K log^2 K) compare-exchange passes of
    min/max on sublane-reshaped registers) -- pure VPU ops, no
    data-dependent control flow.  One shared network serves the plain
    sort, the deviation (MAD) sort, and the weighted variant, which
    carries all N weight planes through the value comparisons and
    selects the cumulative-weight-0.5 crossing per plane.
  * The network wants a power-of-two row count, so the sort operand is
    topped up (in registers, never in HBM) with +inf sentinel rows of
    weight 0; the median/MAD read fixed ranks (K-1)//2 and K//2 of the
    sorted tile, so sentinels never enter.  IRLS masks sentinel rows
    explicitly (0 * inf = nan otherwise).
  * m is tiled in multiples of 128 lanes; the launcher pads M with ZERO
    columns (sentinel +inf columns would make the in-kernel MAD compute
    inf - inf = nan) and strips the pad.
  * Compute is float32 internally regardless of input dtype (bf16
    gradients upcast per tile, bf16 written back -- matches the
    reference).

ONE-RESIDENCY BATCHING (grid and streaming).  The launch grid is
(M_pad // bm, K_pad // bk): each (bk, bm) input block is DMA'd into a
persistent (K_pad, bm) VMEM scratch accumulator, and on the last K step
ALL N neighborhood estimates (the weight columns of a (K, N) combining
matrix) are computed from that single residency.  The N axis lives in
the kernel BODY, not the launch grid, so the number of HBM fetches of
the update matrix is (M_pad/bm) * (K_pad/bk) -- independent of N.  The
pre-batching kernel ran grid (N, M/bm, K/bk) and re-streamed the whole
(K, M) matrix once per weight column: an N x traffic overhead for
diffusion rounds (N = graph size).  ``launch_plan`` is the single
source of truth for the grid/tile geometry and the modeled traffic; the
benchmarks audit it.

Block sizes default to ``kernels.tuning`` (cached autotuner winner, or
a VMEM-budget heuristic when no measurement is cached).

TWO-PASS K-MAJOR PATH (K >> 64).  The single-pass kernel's working set
is dominated by the full-K sort networks: the weighted-median carry
planes and the MAD deviation planes are (next_pow2(K), N, bm) f32, so
large K (and K x N) overflows VMEM.  ``path="two_pass"`` keeps the SAME
(M/bm, K/bk) grid and single input residency but replaces the full-K
sorts with two passes *over the K axis*:

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
(K, N, bm) -- the single-pass VMEM blow-up never re-enters through N.
Input block fetches/bytes are identical to the single-pass plan at
equal tile sizes (one residency, N-free grid), so total modeled HBM
traffic stays ~1x (bounded by 2x via K padding to bk multiples).
``launch_plan`` models both paths (geometry, traffic, VMEM residency)
and auto-selects: two-pass iff K > 64 and the single-pass VMEM model
exceeds ``VMEM_BUDGET_BYTES``; a ``kernels.tuning`` cached winner (the
measured single<->two-pass crossover) takes precedence.
"""

from __future__ import annotations

import functools
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


def _median_rows(x_sorted: jnp.ndarray, k: int) -> jnp.ndarray:
    """Median of the first k (valid) rows of an ascending-sorted tile whose
    pad rows are +inf (and therefore sorted to the end)."""
    lo = x_sorted[(k - 1) // 2]
    hi = x_sorted[k // 2]
    return 0.5 * (lo + hi)


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


def _weighted_median_planes(xs: jnp.ndarray, ws: jnp.ndarray) -> jnp.ndarray:
    """Weighted medians of an ascending-sorted tile, one per (globally
    normalized) weight plane: the cumulative-weight-1/2 crossing."""
    return _wquantile_planes(xs, ws, 0.5)


def _rank_median_planes(xs_sorted: jnp.ndarray, cnt) -> jnp.ndarray:
    """Midpoint median of the first ``cnt`` rows of an ascending-sorted
    tile whose pad rows are +inf.  ``cnt`` may be a traced scalar (the
    K block's valid-row count), so the two ranks are selected by mask
    rather than static indexing.  (P, ...) -> (...)."""
    io = jax.lax.broadcasted_iota(jnp.int32, xs_sorted.shape, 0)
    lo = jnp.sum(jnp.where(io == (cnt - 1) // 2, xs_sorted, 0.0), axis=0)
    hi = jnp.sum(jnp.where(io == cnt // 2, xs_sorted, 0.0), axis=0)
    return 0.5 * (lo + hi)


def _mm_kernel(x_ref, a_ref, o_ref, xs_ref, *, k: int, block_k: int,
               num_iters: int, c: float, weighted: bool):
    """Grid (M/bm, K_pad/bk): stream K blocks into the VMEM scratch
    accumulator; on the last K step compute ALL N estimates from that
    one residency (the N axis is a kernel-body batch, not a grid axis).
    """
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    xs_ref[pl.ds(ki * block_k, block_k), :] = x_ref[...].astype(jnp.float32)

    @pl.when(ki == nk - 1)
    def _compute():
        xp = xs_ref[...]                             # (K_pad, bm), pads=+inf
        k_pad, bm = xp.shape
        n_out = a_ref.shape[1]
        p = next_pow2(k_pad)
        if p != k_pad:    # top up to the network size, in registers only
            xp = jnp.concatenate(
                [xp, jnp.full((p - k_pad, bm), jnp.inf, jnp.float32)], axis=0)
        valid = (jax.lax.broadcasted_iota(jnp.int32, xp.shape, 0) < k)
        x = jnp.where(valid, xp, 0.0)                # masked values for IRLS
        # normalized combination weight columns; sentinel rows are 0
        a = a_ref[...].astype(jnp.float32)           # (K_pad, N)
        if p != k_pad:
            a = jnp.concatenate(
                [a, jnp.zeros((p - k_pad, n_out), jnp.float32)], axis=0)

        # --- robust init: (weighted) median + MAD, one shared sort ---
        if weighted:
            # carry every weight plane through the single value sort
            xs, (ws,) = _bitonic_sort_rows(xp, (_weight_planes(a, bm),))
            med = _weighted_median_planes(xs, ws)    # (N, bm)
        else:
            xs, _ = _bitonic_sort_rows(xp)
            med = _median_rows(xs, k)[None]          # (1, bm)
        # MAD is the plain median of |x - med_n| (matches the oracle);
        # the deviations differ per neighborhood, so sort all N planes
        # at once -- still a single network, trailing dims (N, bm).
        dev = jnp.where(valid[:, None, :],
                        jnp.abs(x[:, None, :] - med[None]), jnp.inf)
        ds, _ = _bitonic_sort_rows(dev)
        scale = jnp.maximum(_MAD_CONSISTENCY * _median_rows(ds, k),
                            _SCALE_FLOOR)            # (N, bm)

        # --- efficient refinement: fixed-T weighted Tukey IRLS, all N ---
        c2 = jnp.float32(c * c)
        xb = x[:, None, :]                           # (P, 1, bm)
        aw = a[:, :, None]                           # (P, N, 1), 0 on pads

        def body(t, mu):
            y = (xb - mu[None]) / scale[None]
            u = jnp.clip(1.0 - (y * y) / c2, 0.0, 1.0)
            w = aw * (u * u)                         # a_k * b_k
            num = jnp.sum(w * xb, axis=0)
            den = jnp.sum(w, axis=0)
            safe = den > _SCALE_FLOOR
            return jnp.where(safe, num / jnp.where(safe, den, 1.0), mu)

        mu = jax.lax.fori_loop(0, num_iters, body, med)
        o_ref[...] = mu.astype(o_ref.dtype)


def _mm_two_pass_kernel(x_ref, a_ref, o_ref, xs_ref, med_ref, mad_ref, *,
                        k: int, block_k: int, n_chunk: int, num_iters: int,
                        c: float, weighted: bool):
    """K-major two-pass kernel (see module docstring).

    Same (M/bm, K_pad/bk) grid and one-residency streaming as the
    single-pass kernel, but the sort networks are bk-sized: pass 1
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
    ``input_block_fetches`` counts (bk, bm) update-matrix blocks DMA'd
    from HBM; it is independent of ``n_out`` by construction (the N axis
    is not a grid axis).
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

    @property
    def total_bytes(self) -> int:
        """Total modeled HBM traffic of one launch.  Both paths stream
        the update matrix exactly once (the two-pass intermediate lives
        in VMEM scratch, never HBM)."""
        return self.input_bytes + self.weight_bytes + self.output_bytes


def single_pass_vmem_bytes(k: int, n: int, block_m: int) -> int:
    """Modeled peak VMEM working set of the single-pass kernel: the
    (K_pad, bm) residency, ~3 (P, bm) f32 sort/mask buffers, and ~5
    (P, N, bm) f32 planes (broadcast weight carries + their sort
    ping-pong, deviations + sorted copy, IRLS y/u/w peak) -- the
    full-K networks carry every weight plane, which is exactly what
    the two-pass path bounds away."""
    k_pad = k + (k % 2)
    p = next_pow2(max(k_pad, 2))
    return 4 * (k_pad * block_m + 3 * p * block_m + 5 * p * n * block_m)


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
                n_chunk: Optional[int] = None) -> LaunchPlan:
    """Resolve the kernel path + tile sizes (via kernels.tuning when
    unset) and derive the grid, modeled HBM traffic and modeled VMEM
    residency for a (K, M) x (K, N) run.  ``path=None`` auto-selects:
    the cached tuning winner for the workload if one names a path, else
    the ``auto_path`` VMEM-crossover heuristic."""
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
    m_total = m + ((-m) % block_m)

    if path == "two_pass":
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
        )

    if block_k is None:
        bk = k + (k % 2)
    else:
        if block_k % 2 != 0 or block_k <= 0:
            raise ValueError(f"block_k must be positive and even, got {block_k}")
        bk = block_k
    k_pad = ((k + bk - 1) // bk) * bk
    grid = (m_total // block_m, k_pad // bk)
    fetches = grid[0] * grid[1]
    return LaunchPlan(
        grid=grid, block_m=block_m, block_k=bk, k_pad=k_pad,
        m_total=m_total, n_out=n,
        input_block_fetches=fetches,
        input_bytes=fetches * bk * block_m * itemsize,
        weight_bytes=k_pad * n * 4,
        output_bytes=n * m_total * itemsize,
        path=path, n_chunk=1, num_k_blocks=k_pad // bk,
        stats_bytes=0,
        vmem_bytes=single_pass_vmem_bytes(k, n, block_m),
    )


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
    else:
        kernel = functools.partial(_mm_kernel, k=k, block_k=bk,
                                   num_iters=num_iters, c=c,
                                   weighted=weighted)
        scratch = (pltpu.VMEM((k_pad, plan.block_m), jnp.float32),)
    return KernelCall(
        kernel=kernel,
        grid=plan.grid,
        in_specs=(
            pl.BlockSpec((bk, plan.block_m), lambda mi, ki: (ki, mi)),
            pl.BlockSpec((k_pad, n_out), lambda mi, ki: (0, 0)),
        ),
        out_specs=pl.BlockSpec((n_out, plan.block_m), lambda mi, ki: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((n_out, plan.m_total), dtype),
        scratch_shapes=scratch,
    )


def _pad_inputs(
    x: jnp.ndarray, a: jnp.ndarray, *, plan: LaunchPlan
) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Pad (K, M) values and (K, N) weights to the plan's grid geometry.

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


def _launch(
    x: jnp.ndarray,
    a: jnp.ndarray,                  # (K, N) normalized weight columns
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
    """Run the fused kernel: (K, M) values x (K, N) weights -> (N, M).

    Weight columns are normalized (and invalid columns replaced by
    uniform) here -- the in-kernel weighted median selects the absolute
    cumulative-weight-0.5 crossing, so unnormalized weights would be
    silently wrong, not just scaled.  ``path`` picks the single-pass or
    two-pass kernel (None = launch_plan's auto selection).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    k, m = x.shape
    if weighted:
        a = location.normalize_weights(a, dtype=jnp.float32)
    n_out = a.shape[1]
    plan = launch_plan(k, m, n_out, dtype=x.dtype,
                       block_m=block_m, block_k=block_k,
                       path=path, n_chunk=n_chunk)
    xp, ap, _ = _pad_inputs(x, a, plan=plan)
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
    return out[:, :m]


def _uniform_weights(k: int) -> jnp.ndarray:
    return jnp.full((k, 1), 1.0 / k, dtype=jnp.float32)


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
    """MM-aggregate a (K, M) array along axis 0 -> (M,) via Pallas.

    ``a`` is an optional (K,) vector of combination weights; it is
    normalized internally (invalid weights fall back to uniform, as in
    ``repro.core.location.normalize_weights``).  Block sizes and the
    kernel path default to the kernels.tuning cache/heuristic.
    """
    if x.ndim != 2:
        raise ValueError(f"mm_aggregate_2d wants (K, M), got {x.shape}")
    k = x.shape[0]
    if a is None:
        aw, weighted = _uniform_weights(k), False
    else:
        if a.shape != (k,):
            raise ValueError(f"weights must be ({k},), got {a.shape}")
        aw, weighted = a.reshape(k, 1), True
    out = _launch(x, aw, weighted=weighted, num_iters=num_iters, c=c,
                  block_m=block_m, block_k=block_k, interpret=interpret,
                  path=path)
    return out[0]


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

    Column n of ``a`` is one neighborhood's combination weights (a_{.n}
    of Eq. 15), normalized internally per column.  The x tile is
    streamed from HBM exactly ONCE regardless of N -- all N estimates
    are computed in the kernel body from the single VMEM residency (see
    the module docstring); this is the diffusion hot path (K, N = graph
    size).  ``path`` selects the single-pass or two-pass (K >> 64)
    kernel; None auto-selects via launch_plan.
    """
    if x.ndim != 2 or a.ndim != 2 or a.shape[0] != x.shape[0]:
        raise ValueError(
            f"want x (K, M) and a (K, N), got {x.shape} and {a.shape}")
    return _launch(x, a, weighted=True, num_iters=num_iters, c=c,
                   block_m=block_m, block_k=block_k, interpret=interpret,
                   path=path, n_chunk=n_chunk)
