"""Pallas TPU kernels for the perf-critical aggregation hot-spot.

mm_aggregate.py -- fused (weighted) median/MAD/Tukey-IRLS over the
                   agent axis; ALL N neighborhood weight columns are
                   batched in the kernel body, so the update matrix is
                   streamed from HBM exactly once per launch
                   (one-residency).  Two lowerings: the single-pass
                   full-K-sort kernel (small meshes) on (K, R, C) views
                   of each leaf, and the two-pass K-major kernel
                   (per-K-block stats + cross-block IRLS) on (K, M) for
                   K >> 64; launch_plan models both and auto-selects
                   (see docs/kernels.md)
ops.py          -- AggregationEngine: the repo-wide aggregation entry
                   point (array / batched / whole-pytree single launch)
tuning.py       -- block_m/block_k/path autotuner + heuristic; the
                   engine consults its cache (incl. the measured
                   single<->two-pass crossover) by default
ref.py          -- pure-jnp oracle (tests assert kernel == ref)
"""

from repro.kernels import mm_aggregate, ops, ref, tuning  # noqa: F401
from repro.kernels.ops import (  # noqa: F401
    AggregationEngine,
    get_engine,
    mm_aggregate as aggregate,
    mm_aggregate_batched as aggregate_batched,
    mm_aggregate_tree as aggregate_tree,
)
