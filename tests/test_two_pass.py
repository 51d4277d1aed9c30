"""The two-pass K-major kernel vs the jnp oracle + its launch model.

Coverage per the large-cohort acceptance contract:
  * parity sweep K in {64, 128, 512} x N in {1, 32} x {f32, bf16}
    against ref.mm_aggregate_batched_ref, under contamination -- with
    the default geometry (one power-of-two K block up to 512, KB == 1)
    the two-pass kernel computes the *identical* statistic, so the
    existing single-pass tolerances apply unchanged;
  * the KB > 1 regime (K blocks smaller than K: median-of-medians
    init + pooled MAD scale) is approximate by design -- robustness is
    preserved up to the breakdown point, and the K=1024 default split
    (KB=2) stays within a tight statistical tolerance of the oracle;
  * launch_plan audits: two-pass input bytes independent of N, total
    modeled HBM traffic <= 2x the single-pass model at equal (K,M,N),
    modeled VMEM residency <= budget where the single-pass plan
    overflows, and the auto crossover rules;
  * tuning: the cached crossover winner (path) round-trips through the
    in-process cache, the persistent JSON file, and the engine.

Interpret-mode note: large-K cells force a single N chunk -- chunked
and unchunked lowerings are algorithmically identical (chunk
invariance is asserted separately on a small shape), but interpret
mode pays per-dispatch overhead per chunk.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import mm_aggregate as K
from repro.kernels import ops, ref, tuning


def _problem(k, m, n, dtype=jnp.float32, contaminate=0.3, seed=None):
    kx, ka = jax.random.split(jax.random.key(seed or (k * 1000 + n)))
    x = jax.random.normal(kx, (k, m)).astype(dtype)
    nmal = int(contaminate * k)
    if nmal:
        x = x.at[-nmal:].add(100.0)
    a = jax.random.uniform(ka, (k, n), minval=0.0, maxval=1.0)
    return x, a


# ---------------------------------------------------------------------------
# parity: default geometry (KB == 1) is exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,n,dtype", [
    (64, 1, jnp.float32),
    (64, 32, jnp.float32),
    (64, 32, jnp.bfloat16),
    (128, 1, jnp.bfloat16),
    (128, 32, jnp.float32),
    (512, 1, jnp.float32),
    (512, 1, jnp.bfloat16),
    (512, 32, jnp.float32),
])
def test_two_pass_parity_sweep(k, n, dtype):
    m = 333 if k == 64 else 120      # non-lane-multiple M exercises the pad
    x, a = _problem(k, m, n, dtype=dtype)
    nc = n if k >= 128 else None     # one chunk: interpret dispatch cost
    got = K.mm_aggregate_batched_2d(x, a, interpret=True, path="two_pass",
                                    n_chunk=nc)
    want = ref.mm_aggregate_batched_ref(x, a)
    assert got.shape == (n, m) and got.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=5e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_two_pass_unweighted_matches_oracle():
    x, _ = _problem(512, 257, 1)
    got = K.mm_aggregate_2d(x, interpret=True, path="two_pass")
    np.testing.assert_allclose(got, ref.mm_aggregate_ref(x), atol=1e-5)


def test_two_pass_odd_k_partial_last_block():
    """K=513 -> bk=512, KB=2, last block holds a single valid row."""
    x, a = _problem(513, 130, 3, seed=7)
    got = K.mm_aggregate_batched_2d(x, a, interpret=True, path="two_pass")
    want = ref.mm_aggregate_batched_ref(x, a)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=5e-3)


def test_two_pass_n_chunk_invariance():
    """Chunked and unchunked N processing must agree exactly."""
    x, a = _problem(128, 200, 7, seed=11)
    outs = [K.mm_aggregate_batched_2d(x, a, interpret=True, path="two_pass",
                                      n_chunk=nc) for nc in (1, 3, 7)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=0)


# ---------------------------------------------------------------------------
# KB > 1: the approximate regime
# ---------------------------------------------------------------------------

def test_two_pass_k1024_default_split_near_oracle():
    """K=1024 auto-splits into KB=2 blocks of 512: the
    median-of-medians init / pooled MAD scale shift the Tukey fixed
    point only marginally (measured max |err| ~0.02 at 30%
    contamination; asserted with 5x margin)."""
    x, _ = _problem(1024, 257, 1, seed=3)
    plan = K.launch_plan(1024, 257, 1, path="two_pass")
    assert plan.num_k_blocks == 2
    got = K.mm_aggregate_2d(x, interpret=True, path="two_pass")
    want = ref.mm_aggregate_ref(x)
    err = np.abs(np.asarray(got) - np.asarray(want))
    assert err.max() < 0.1, err.max()
    assert err.mean() < 0.02, err.mean()


@pytest.mark.parametrize("contaminate", [0.3, 0.4])
def test_two_pass_kb_gt1_preserves_breakdown(contaminate):
    """Forced small blocks (KB=8) under contiguous-tail contamination:
    whole K blocks are fully malicious, and the mass-weighted
    median-of-medians must still reject them (the init keeps the
    breakdown property block-wise)."""
    x = jax.random.normal(jax.random.key(17), (512, 256))
    clean = ref.mm_aggregate_ref(x[: int(512 * (1 - contaminate))])
    x = x.at[-int(contaminate * 512):].set(1e5)
    got = K.mm_aggregate_2d(x, interpret=True, path="two_pass", block_k=64)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.max(jnp.abs(got - clean))) < 2.0


def test_two_pass_block_k_validation():
    with pytest.raises(ValueError, match="power of two"):
        K.launch_plan(100, 128, 1, path="two_pass", block_k=48)


# ---------------------------------------------------------------------------
# launch_plan audits: traffic + VMEM models, crossover
# ---------------------------------------------------------------------------

def test_two_pass_input_bytes_independent_of_n():
    for k in (128, 512, 1024):
        plans = {n: K.launch_plan(k, 1 << 14, n, block_m=128,
                                  path="two_pass") for n in (1, 8, 32)}
        assert len({p.input_block_fetches for p in plans.values()}) == 1
        assert len({p.input_bytes for p in plans.values()}) == 1


@pytest.mark.parametrize("k,m,n", [
    (128, 1 << 14, 1), (512, 4096, 1), (512, 4096, 32),
    (513, 4096, 3), (1024, 4096, 1), (65, 4096, 8),
])
def test_two_pass_traffic_within_2x_single(k, m, n):
    """Total modeled HBM traffic of the two-pass plan stays <= 2x the
    single-pass model at equal (K, M, N): both stream the update tile
    once (the stat intermediate never round-trips HBM); the only
    overhead is K padding to a power-of-two block multiple."""
    two = K.launch_plan(k, m, n, block_m=128, path="two_pass")
    one = K.launch_plan(k, m, n, block_m=128, path="single")
    assert two.stats_bytes > 0 and two.path == "two_pass"
    assert two.total_bytes <= 2 * one.total_bytes, (two, one)


def test_two_pass_vmem_bounded_where_single_overflows():
    """The acceptance geometry: a 512-agent cohort at block_m=256.  The
    single-pass model overflows the budget (full-K sort carries); the
    two-pass model fits with room to spare."""
    one = K.launch_plan(512, 4096, 1, block_m=256, path="single")
    two = K.launch_plan(512, 4096, 1, block_m=256, path="two_pass")
    assert one.vmem_bytes > K.VMEM_BUDGET_BYTES
    assert two.vmem_bytes <= K.VMEM_BUDGET_BYTES
    # and that is exactly where the auto crossover engages
    assert K.launch_plan(512, 4096, 1, block_m=256).path == "two_pass"


def test_auto_path_keeps_small_meshes_single():
    """K <= 64 stays on the measured single-pass path whatever the
    VMEM model says (bit-stability for every pre-two-pass workload),
    and small workloads never flip."""
    assert K.auto_path(64, 64, 128) == "single"
    assert K.auto_path(8, 1, 512) == "single"
    assert K.launch_plan(8, 4096, 1).path == "single"
    assert K.launch_plan(64, 1 << 14, 32, block_m=128).path == "single"
    # large-K low-dim stays single too (the residency fits at bm=128)
    assert K.launch_plan(512, 8, 1).path == "single"


def test_plan_vmem_and_path_fields_in_asdict():
    """The runner's launch audit serializes the plan via _asdict: the
    new fields must ride along (BENCH consumers key on them)."""
    d = K.launch_plan(512, 4096, 1, block_m=256)._asdict()
    assert {"path", "vmem_bytes", "n_chunk", "num_k_blocks",
            "stats_bytes"} <= set(d)


# ---------------------------------------------------------------------------
# tuning: crossover winner caching (in-process, persistent, engine)
# ---------------------------------------------------------------------------

def test_tuning_choice_path_roundtrip(tmp_path, monkeypatch):
    shape = (300, 777, 2)
    tuning.clear_cache()
    try:
        tuning.set_blocks(*shape, jnp.float32, (128, 64, "two_pass"))
        choice = tuning.get_choice(*shape)
        assert choice == tuning.TuneChoice(128, 64, "two_pass")
        assert tuning.get_blocks(*shape) == (128, 64)   # legacy surface
        plan = K.launch_plan(*shape)
        assert plan.path == "two_pass" and plan.block_k == 64
        # persistent JSON round-trip keeps the path
        path = str(tmp_path / "tune.json")
        assert tuning.save_cache(path) == path
        entry = [e for e in json.load(open(path))["entries"]
                 if e["k"] == 300][0]
        assert entry["path"] == "two_pass"
        tuning.clear_cache()
        assert tuning.load_cache(path) >= 1
        assert tuning.get_choice(*shape) == choice
        # pre-two-pass entries (no "path" key) still load, path=None
        del entry["path"]
        json.dump({"version": 1, "entries": [entry]},
                  open(path, "w"))
        tuning.clear_cache()
        assert tuning.load_cache(path) == 1
        assert tuning.get_choice(*shape).path is None
    finally:
        tuning.clear_cache()


def test_cached_single_block_k_not_reused_for_two_pass():
    """A cached single-pass winner whose block_k is not a power of two
    must not leak into an auto-selected two-pass plan (its K split
    belongs to the other kernel's geometry)."""
    shape = (512, 4096, 1)
    tuning.clear_cache()
    try:
        tuning.set_blocks(*shape, jnp.float32, (256, 6))   # path=None
        plan = K.launch_plan(*shape)
        assert plan.path == "two_pass"          # auto crossover at K=512
        assert plan.block_k == K.two_pass_block_k(512)
    finally:
        tuning.clear_cache()


def test_autotune_caches_two_pass_winner_and_engine_consults():
    shape = (96, 200, 1)
    tuning.clear_cache()
    try:
        choice = tuning.autotune(*shape, interpret=True, reps=1,
                                 candidates=((128, 32, "two_pass"),))
        assert choice == (128, 32)
        assert tuning.get_choice(*shape).path == "two_pass"
        with ops.record_workloads() as rec:
            x = jax.random.normal(jax.random.key(0), (96, 200))
            out = ops.mm_aggregate(x, interpret=True)
        assert rec[0]["path"] == "two_pass" and rec[0]["block_k"] == 32
        np.testing.assert_allclose(out, ref.mm_aggregate_ref(x), atol=0.05)
    finally:
        tuning.clear_cache()


@pytest.mark.parametrize("k,dtype", [(20, jnp.float32), (32, jnp.float32),
                                     (48, jnp.bfloat16), (64, jnp.bfloat16)])
def test_candidate_k_splits_are_whole_sublane_tiles(k, dtype):
    """A K block shorter than the padded K axis must be a multiple of
    the vreg row tile (8 rows f32, 16 bf16) or Mosaic refuses it."""
    rows = tuning.sublane_rows(dtype)
    for bm, bk in tuning.candidate_blocks(k, 4096, 1, dtype):
        assert bk is None or bk % rows == 0, (bm, bk)
    splits = {bk for _, bk in tuning.candidate_blocks(k, 4096, 1, dtype)}
    assert (None in splits) and (len(splits) == 2) == (k in (32, 64))


def test_autotune_raises_on_a_refused_candidate():
    tuning.clear_cache()
    try:
        with pytest.raises(ValueError, match="block_k"):
            tuning.autotune(8, 256, 1, interpret=True, reps=1,
                            candidates=((128, 3),))
        assert tuning.cache_size() == 0
    finally:
        tuning.clear_cache()


def test_candidate_choices_include_crossover_for_large_k():
    paths = {c.path for c in tuning.candidate_choices(256, 1 << 14, 1)}
    assert "two_pass" in paths
    # small meshes sweep single-pass only
    assert {c.path for c in tuning.candidate_choices(8, 4096, 1)} == \
        {"single"}


# ---------------------------------------------------------------------------
# engine end to end
# ---------------------------------------------------------------------------

def test_engine_routes_large_k_to_two_pass():
    """ops.mm_aggregate at K=512 x block_m=256 auto-selects the
    two-pass kernel (recorded in the workload audit) and still matches
    the oracle exactly (KB == 1)."""
    x, _ = _problem(512, 300, 1, seed=21)
    with ops.record_workloads() as rec:
        out = ops.mm_aggregate(x, interpret=True, block_m=256)
    assert rec[0]["path"] == "two_pass"
    np.testing.assert_allclose(out, ref.mm_aggregate_ref(x), atol=1e-5)


def test_engine_forced_path_and_tree():
    """An explicit engine path override flows through the whole-pytree
    launch; the two-pass tree aggregate matches per-leaf oracles."""
    key = jax.random.key(5)
    tree = {
        "w": jax.random.normal(key, (96, 32, 8)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (96, 17)),
    }
    a = jax.random.uniform(jax.random.fold_in(key, 2), (96,),
                           minval=0.1, maxval=1.0)
    eng = ops.AggregationEngine(interpret=True, path="two_pass")
    got = eng.aggregate_tree(tree, a)
    want = jax.tree.map(lambda l: ref.mm_aggregate_ref(l, a), tree)
    for k2 in tree:
        np.testing.assert_allclose(got[k2], want[k2], atol=1e-5, err_msg=k2)


def test_engine_rejects_unknown_path():
    with pytest.raises(ValueError, match="path"):
        ops.AggregationEngine(path="three_pass")
