"""Pallas MM-aggregation kernel benchmark (the kernel runs in interpret
mode on CPU, where wall-clock is indicative only; the structural win is
HBM-residency fusion, quantified as modeled bytes moved).

The batched rows quantify the one-residency fix: the pre-fix kernel
put the N weight-column axis in the launch grid and re-streamed the
whole (K, M) update matrix once per column (``one_residency=False``);
the current kernel batches N in the kernel body and streams each input
tile exactly once (``one_residency=True``) -- an N x input-traffic
reduction for diffusion-sized N.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro import compat
from repro.kernels import ops, ref


def _time(fn, x, reps=3):
    jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(x))
    return (time.perf_counter() - t0) / reps * 1e6


def modeled_hbm_bytes(k: int, m: int, fused: bool, n: int = 1,
                      one_residency: bool = True) -> int:
    """Bytes moved per aggregation of (K, M) f32 against N weight columns.

    fused + one_residency : 1 read of the tile + weights + N-row write
                            (the current batched kernel)
    fused, not one_resid.  : N reads of the tile (pre-fix grid that
                            re-streamed the input per weight column)
    unfused jnp            : per column, two sorts (r+w each) and T=10
                            IRLS passes (r each)
    """
    tile = k * m * 4
    weights = k * n * 4 if n > 1 else 0
    out = n * m * 4
    if fused:
        reads = tile if one_residency else n * tile
        return reads + weights + out
    return n * (2 * 2 * tile + 10 * tile) + weights + out


def main() -> list[tuple]:
    rows = []
    for k, m in ((16, 1 << 15), (32, 1 << 15), (64, 1 << 14)):
        x = jax.random.normal(jax.random.key(0), (k, m))
        t_kernel = _time(jax.jit(
            lambda v: ops.mm_aggregate(v)), x)
        t_ref = _time(jax.jit(ref.mm_aggregate_ref), x)
        rows.append((f"kernel/mm_pallas/K{k}_M{m}", t_kernel,
                     modeled_hbm_bytes(k, m, True)))
        rows.append((f"kernel/mm_ref_jnp/K{k}_M{m}", t_ref,
                     modeled_hbm_bytes(k, m, False)))
        # batched traffic model: the tentpole's win, pre- vs post-fix
        # (timing capped at N=16 to keep interpret-mode wall clock sane;
        # the modeled ratio scales linearly in N either way)
        for n in sorted({8, min(16, k)}):
            pre = modeled_hbm_bytes(k, m, True, n=n, one_residency=False)
            post = modeled_hbm_bytes(k, m, True, n=n, one_residency=True)
            a = jax.random.uniform(jax.random.key(1), (k, n),
                                   minval=0.1, maxval=1.0)
            t_b = _time(jax.jit(
                lambda v, w=a: ops.mm_aggregate_batched(v, w)), x)
            rows.append((f"kernel/mm_pallas_batched/K{k}_M{m}_N{n}"
                         f"_traffic_x{pre / post:.1f}", t_b, post))
    # large-cohort single- vs two-pass crossover: same one-residency
    # traffic model (the two-pass stat intermediates never touch HBM),
    # wall clock decides -- the sort work drops from one next_pow2(K)
    # network to K/bk blocks of bk plus a tiny combine.
    for k, m in ((256, 1 << 13), (512, 1 << 12)):
        x = jax.random.normal(jax.random.key(2), (k, m))
        for path in ("single", "two_pass"):
            t_p = _time(jax.jit(
                lambda v, _p=path: ops.mm_aggregate(v, path=_p)), x)
            rows.append((f"kernel/mm_pallas_{path}/K{k}_M{m}", t_p,
                         modeled_hbm_bytes(k, m, True)))
    return rows


if __name__ == "__main__":
    compat.enable_persistent_compilation_cache()
    for name, us, derived in main():
        print(f"{name},{us:.2f},{derived}")
