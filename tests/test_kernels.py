"""Pallas MM-aggregation kernel vs the pure-jnp oracle (ref.py).

Shape/dtype sweep in interpret mode (CPU) per the kernel-validation
contract: every (K, M, dtype, weights, contamination) combination must
match ref.mm_aggregate_ref to float tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import mm_aggregate as K
from repro.kernels import ops, ref


@pytest.mark.parametrize("k", [2, 3, 4, 5, 8, 16, 31, 32, 64])
@pytest.mark.parametrize("m", [1, 7, 128, 513])
def test_shape_sweep_f32(k, m):
    x = jax.random.normal(jax.random.key(k * 1000 + m), (k, m))
    nmal = max(0, int(0.3 * k))
    if nmal:
        x = x.at[-nmal:].add(100.0)
    got = ops.mm_aggregate(x, interpret=True)
    want = ref.mm_aggregate_ref(x)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtype_sweep(dtype):
    x = jax.random.normal(jax.random.key(0), (16, 1000)).astype(dtype)
    got = ops.mm_aggregate(x, interpret=True)
    want = ref.mm_aggregate_ref(x)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("block_m", [128, 256, 1024])
def test_block_size_invariance(block_m):
    x = jax.random.normal(jax.random.key(3), (8, 777))
    got = ops.mm_aggregate(x, interpret=True, block_m=block_m)
    want = ref.mm_aggregate_ref(x)
    np.testing.assert_allclose(got, want, atol=1e-5)


@given(seed=st.integers(0, 10_000), k=st.integers(2, 24),
       m=st.integers(1, 300))
@settings(max_examples=20, deadline=None)
def test_property_matches_ref(seed, k, m):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(k, m)).astype(np.float32) * 10)
    got = ops.mm_aggregate(x, interpret=True)
    want = ref.mm_aggregate_ref(x)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_bitonic_sort_network():
    x = jax.random.normal(jax.random.key(1), (16, 37))
    got, _ = K._bitonic_sort_rows(x)
    want = jnp.sort(x, axis=0)
    np.testing.assert_allclose(got, want)


def test_higher_rank_input():
    x = jax.random.normal(jax.random.key(2), (8, 12, 5, 3))
    got = ops.mm_aggregate(x, interpret=True)
    want = ref.mm_aggregate_ref(x)
    assert got.shape == (12, 5, 3)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_tree_launch_matches_per_leaf():
    key = jax.random.key(5)
    tree = {
        "w": jax.random.normal(key, (8, 64, 32)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (8, 17)),
        "s": jax.random.normal(jax.random.fold_in(key, 2), (8,)) ,
    }
    got = ops.mm_aggregate_tree(tree, interpret=True)
    want = jax.tree.map(lambda l: ref.mm_aggregate_ref(l), tree)
    for k2 in tree:
        np.testing.assert_allclose(got[k2], want[k2], atol=1e-5, err_msg=k2)


def test_kernel_robustness():
    """The fused kernel preserves the breakdown property."""
    x = jax.random.normal(jax.random.key(7), (32, 256))
    clean = ref.mm_aggregate_ref(x[:23])
    x = x.at[23:].set(1e5)   # 28% contamination
    got = ops.mm_aggregate(x, interpret=True)
    assert float(jnp.max(jnp.abs(got - clean))) < 2.0


def test_kernel_grad_safe():
    """The kernel path is used in serving/aggregation (no grad), but it
    should at least not produce NaN under jit."""
    x = jax.random.normal(jax.random.key(8), (4, 100))
    out = jax.jit(lambda v: ops.mm_aggregate(v, interpret=True))(x)
    assert bool(jnp.isfinite(out).all())


def test_kernel_as_registry_aggregator():
    """mm_pallas (the fused kernel) is a drop-in aggregator and matches
    mm_tukey exactly on uniform weights."""
    import jax
    import jax.numpy as jnp
    from repro.core import aggregators

    x = jax.random.normal(jax.random.key(11), (16, 300))
    x = x.at[-4:].add(50.0)
    a = aggregators.get_aggregator("mm_pallas")(x, None)
    b = aggregators.get_aggregator("mm_tukey")(x, None)
    np.testing.assert_allclose(a, b, atol=1e-5)


# ---------------------------------------------------------------------------
# weighted-kernel parity sweep (satellite: Pallas `a`-weighted output vs
# the location.mm_estimate jnp oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [2, 3, 5, 8, 32])
@pytest.mark.parametrize("m", [1, 7, 513])
@pytest.mark.parametrize("contaminated", [False, True])
def test_weighted_parity_f32(k, m, contaminated):
    key = jax.random.key(k * 10_000 + m + int(contaminated))
    kx, ka = jax.random.split(key)
    x = jax.random.normal(kx, (k, m))
    if contaminated:
        nmal = max(1, int(0.3 * k))
        x = x.at[-nmal:].add(100.0)
    a = jax.random.uniform(ka, (k,), minval=0.05, maxval=2.0)
    got = ops.mm_aggregate(x, a, interpret=True)
    want = ref.mm_aggregate_ref(x, a)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_weighted_parity_dtypes(dtype):
    kx, ka = jax.random.split(jax.random.key(42))
    x = jax.random.normal(kx, (16, 1000)).astype(dtype)
    x = x.at[-4:].add(50.0)
    a = jax.random.uniform(ka, (16,), minval=0.1, maxval=1.0)
    got = ops.mm_aggregate(x, a, interpret=True)
    want = ref.mm_aggregate_ref(x, a)
    assert got.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_batched_neighborhoods_match_oracle():
    """One kernel launch over all N weight columns == per-column oracle."""
    kx, ka = jax.random.split(jax.random.key(7))
    x = jax.random.normal(kx, (8, 300))
    x = x.at[-2:].add(50.0)
    a = jax.random.uniform(ka, (8, 8), minval=0.0, maxval=1.0)
    got = ops.mm_aggregate_batched(x, a, interpret=True)
    want = ref.mm_aggregate_batched_ref(x, a)
    assert got.shape == (8, 300)
    np.testing.assert_allclose(got, want, atol=1e-5)


# acceptance sweep: the one-residency batched kernel vs the oracle for
# N>1 with non-divisible K and M, both dtypes, with contamination
@pytest.mark.parametrize("k", [3, 16, 33])
@pytest.mark.parametrize("n", [1, 5, 32])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_parity_sweep(k, n, dtype):
    m = 333   # deliberately not a multiple of any lane tile
    kx, ka = jax.random.split(jax.random.key(k * 100 + n))
    x = jax.random.normal(kx, (k, m)).astype(dtype)
    nmal = max(1, int(0.3 * k))
    x = x.at[-nmal:].add(100.0)
    a = jax.random.uniform(ka, (k, n), minval=0.0, maxval=1.0)
    got = ops.mm_aggregate_batched(x, a, interpret=True)
    want = ref.mm_aggregate_batched_ref(x, a)
    assert got.shape == (n, m) and got.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=5e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_batched_block_invariance():
    """Batched output must not depend on the tile sizes."""
    kx, ka = jax.random.split(jax.random.key(29))
    x = jax.random.normal(kx, (17, 450))
    a = jax.random.uniform(ka, (17, 6), minval=0.0, maxval=1.0)
    want = ref.mm_aggregate_batched_ref(x, a)
    for bm in (128, 512):
        for bk in (None, 6, 18):
            got = ops.mm_aggregate_batched(x, a, interpret=True,
                                           block_m=bm, block_k=bk)
            np.testing.assert_allclose(got, want, atol=1e-5,
                                       err_msg=f"bm={bm} bk={bk}")


def test_input_stream_independent_of_n():
    """One-residency contract: at fixed tile sizes, the number of input
    blocks fetched from HBM (and the bytes streamed) is the same for
    every N -- the weight-column axis lives in the kernel body, not the
    launch grid."""
    fetches = {
        n: K.launch_plan(32, 1 << 14, n, block_m=256).input_block_fetches
        for n in (1, 5, 32)}
    assert len(set(fetches.values())) == 1, fetches
    in_bytes = {
        n: K.launch_plan(32, 1 << 14, n, block_m=256).input_bytes
        for n in (1, 5, 32)}
    assert len(set(in_bytes.values())) == 1, in_bytes
    # and the batched entry point is still exactly ONE pallas_call
    x = jnp.zeros((8, 256))
    a = jnp.full((8, 4), 0.25)
    assert _count_pallas_calls(
        lambda v, w: ops.mm_aggregate_batched(v, w, interpret=True),
        x, a) == 1


def _count_pallas_calls(fn, *args) -> int:
    def walk(jaxpr) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    inner = v.jaxpr if hasattr(v.jaxpr, "eqns") else v
                    n += walk(inner)
        return n
    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


def test_block_k_streaming_invariance():
    """The 2-D (K, M) grid streams K blocks through VMEM scratch; the
    result must not depend on the K block size."""
    x = jax.random.normal(jax.random.key(9), (32, 700))
    a = jax.random.uniform(jax.random.key(10), (32,), minval=0.1, maxval=1.0)
    want = ref.mm_aggregate_ref(x, a)
    for bk in (2, 8, 16):
        got = ops.mm_aggregate(x, a, interpret=True, block_k=bk)
        np.testing.assert_allclose(got, want, atol=1e-5, err_msg=f"bk={bk}")


def test_m_padding_is_zero_not_inf():
    """Regression: the M pad used +inf columns, so the in-kernel MAD
    computed inf - inf = NaN.  The pad must be inert zeros."""
    x = jax.random.normal(jax.random.key(3), (5, 130))
    a = jnp.full((5,), 0.2)
    plan = K.launch_plan(5, 130, 1, block_m=512)
    xp, ap, _ = K._pad_inputs(x, a.reshape(5, 1), plan=plan)
    assert xp.shape == (6, 512)
    pad_cols = xp[:, 130:]
    assert bool(jnp.isfinite(pad_cols).all()), "M pad must be finite"
    np.testing.assert_allclose(pad_cols, 0.0)
    # K pad rows stay +inf sentinels (sorted to the end), weight 0
    assert bool(jnp.isinf(xp[5, :130]).all())
    np.testing.assert_allclose(ap[5], 0.0)


def test_kernel_clean_under_debug_nans():
    """The whole entry point runs with jax_debug_nans enabled on shapes
    that exercise both the K and M padding paths."""
    try:
        jax.config.update("jax_debug_nans", True)
        for shape in ((5, 130), (3, 1), (8, 513)):
            x = jax.random.normal(jax.random.key(shape[0]), shape)
            out = K.mm_aggregate_2d(x, interpret=True)
            assert bool(jnp.isfinite(out).all()), shape
            a = jnp.arange(1.0, shape[0] + 1.0) / shape[0]
            out = K.mm_aggregate_2d(x, a / jnp.sum(a), interpret=True)
            assert bool(jnp.isfinite(out).all()), shape
    finally:
        jax.config.update("jax_debug_nans", False)


@pytest.mark.parametrize("path", ["single", "two_pass"])
@pytest.mark.parametrize("k", [3, 6, 7, 12, 24])
def test_uniform_weight_ties_match_oracle(k, path):
    """Uniform weights put the cumulative-weight crossing exactly on 1/2
    at even K: the kernel's prefix scan must pick the same row as the
    oracle's cumsum, at odd and even K, on both paths."""
    x = jax.random.normal(jax.random.key(k), (k, 300))
    a = jnp.ones((k,))
    got = K.mm_aggregate_2d(x, a, interpret=True, path=path)
    np.testing.assert_allclose(got, ref.mm_aggregate_ref(x, a), atol=1e-5)


def test_zero_weights_fall_back_to_uniform():
    """All-zero (or negative-sum) weights must not NaN: the engine falls
    back to uniform combination weights."""
    x = jax.random.normal(jax.random.key(11), (8, 64))
    uniform = jnp.full((8,), 1.0 / 8)
    for bad in (jnp.zeros((8,)), -jnp.ones((8,))):
        got = ops.mm_aggregate(x, bad, interpret=True)
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(
            got, ops.mm_aggregate(x, uniform, interpret=True), atol=1e-6)


# ---------------------------------------------------------------------------
# AggregationEngine
# ---------------------------------------------------------------------------

def test_engine_tree_weighted_single_launch():
    key = jax.random.key(5)
    tree = {
        "w": jax.random.normal(key, (8, 64, 32)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (8, 17)),
        "s": jax.random.normal(jax.random.fold_in(key, 2), (8,)),
    }
    a = jax.random.uniform(jax.random.fold_in(key, 3), (8,),
                           minval=0.1, maxval=1.0)
    eng = ops.AggregationEngine(interpret=True)
    got = eng.aggregate_tree(tree, a)
    want = jax.tree.map(lambda l: ref.mm_aggregate_ref(l, a), tree)
    for k2 in tree:
        np.testing.assert_allclose(got[k2], want[k2], atol=1e-5, err_msg=k2)


def test_engine_caches_tree_layout():
    tree = {"w": jnp.ones((4, 8)), "b": jnp.zeros((4, 3))}
    eng = ops.AggregationEngine(interpret=True)
    eng.aggregate_tree(tree)
    assert len(eng._layouts) == 1
    eng.aggregate_tree(jax.tree.map(lambda l: l + 1.0, tree))
    assert len(eng._layouts) == 1     # same structure -> cached plan
    eng.aggregate_tree({"w": jnp.ones((4, 9)), "b": jnp.zeros((4, 3))})
    assert len(eng._layouts) == 2     # new shapes -> new plan


def test_engine_tree_donated_matches_undonated():
    """donate_leaves=True must be numerically identical (it only allows
    XLA to reuse the leaf buffers for staging)."""
    def mk():
        key = jax.random.key(9)
        return {"w": jax.random.normal(key, (4, 32, 8)),
                "b": jax.random.normal(jax.random.fold_in(key, 1), (4, 5))}
    want = ops.AggregationEngine(interpret=True).aggregate_tree(mk())
    got = ops.AggregationEngine(
        interpret=True, donate_leaves=True).aggregate_tree(mk())
    for k2 in want:
        np.testing.assert_allclose(got[k2], want[k2], atol=1e-6, err_msg=k2)


def test_tuning_cache_and_engine_consult():
    """get_blocks falls back to the heuristic; a cached (auto)tuned
    winner takes precedence and the default engine picks it up."""
    from repro.kernels import tuning

    shape = (7, 999, 3)   # unlikely to collide with other tests
    tuning.clear_cache()
    try:
        bm0, bk0 = tuning.get_blocks(*shape)
        assert bm0 % 128 == 0 and (bk0 is None or bk0 % 2 == 0)
        tuning.set_blocks(*shape, jnp.float32, (256, None))
        assert tuning.get_blocks(*shape) == (256, None)
        # pinned winner flows through the engine's block resolution
        eng = ops.AggregationEngine(interpret=True)
        x = jnp.zeros((shape[0], shape[1]))
        assert eng._blocks_for(x, *shape) == (256, None)
        # explicit engine block_m still wins over the cache
        eng2 = ops.AggregationEngine(interpret=True, block_m=128)
        assert eng2._blocks_for(x, *shape)[0] == 128
    finally:
        tuning.clear_cache()


def test_autotune_sweeps_and_caches():
    from repro.kernels import tuning

    tuning.clear_cache()
    try:
        choice = tuning.autotune(5, 200, 2, interpret=True, reps=1,
                                 candidates=((128, None), (256, None)))
        assert choice in ((128, None), (256, None))
        assert tuning.get_blocks(5, 200, 2) == choice
        assert tuning.cache_size() == 1
        # idempotent: second call hits the cache (no timing)
        assert tuning.autotune(5, 200, 2, interpret=True) == choice
        # the tuned choice produces oracle-correct results
        x = jax.random.normal(jax.random.key(0), (5, 200))
        a = jax.random.uniform(jax.random.key(1), (5, 2))
        got = ops.mm_aggregate_batched(x, a, interpret=True)
        np.testing.assert_allclose(
            got, ref.mm_aggregate_batched_ref(x, a), atol=1e-5)
    finally:
        tuning.clear_cache()


def test_engine_backends_agree():
    x = jax.random.normal(jax.random.key(21), (8, 257))
    a = jax.random.uniform(jax.random.key(22), (8,), minval=0.0, maxval=1.0)
    pal = ops.mm_aggregate(x, a, interpret=True, backend="pallas")
    jnpb = ops.mm_aggregate(x, a, backend="jnp")
    np.testing.assert_allclose(pal, jnpb, atol=1e-5)


def test_train_step_use_kernel_matches_jnp():
    """ParallelConfig.use_kernel routes the train step's aggregation
    through the Pallas engine; the estimator (and therefore the loss
    trajectory) is identical to the jnp backend."""
    from repro import compat
    from repro.configs.base import ModelConfig, ParallelConfig
    from repro.launch import steps
    from repro.models import model as M
    from repro.optim import optimizers

    mesh = compat.make_mesh((1, 1), ("data", "model"))
    cfg = ModelConfig(name="t", arch_type="dense", num_layers=1, d_model=32,
                      num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64)
    opt_cfg = optimizers.OptimizerConfig(learning_rate=5e-3, warmup_steps=2,
                                         total_steps=50)
    params = M.init_model(jax.random.key(0), cfg)
    opt = optimizers.init(opt_cfg, params)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 17), 0, 64,
                                          dtype=jnp.int32)}
    losses = {}
    for uk in (False, True):
        par = ParallelConfig(aggregation="gather_mm", use_kernel=uk)
        step, _ = steps.make_train_step_gspmd(cfg, par, opt_cfg, mesh)
        js = jax.jit(step)
        p, o = params, opt
        for _ in range(2):
            p, o, m = js(p, o, batch)
        losses[uk] = float(m["loss"])
    assert losses[True] == pytest.approx(losses[False], abs=1e-5)


def test_kernel_in_weighted_diffusion_loop():
    """mm_pallas on a NON-uniform sparse neighborhood (ring graph):
    every a_{.k} column runs inside the batched kernel and the loop
    converges robustly -- the weighted path, end to end."""
    from repro.core import attacks, diffusion, graph
    from repro.data import synthetic

    prob = synthetic.LinearModelProblem(dim=6)
    comb = graph.metropolis_weights(graph.ring(8, hops=2))
    byz = attacks.ByzantineConfig(num_malicious=1, attack="additive",
                                  attack_kwargs=(("delta", 100.0),))
    cfg = diffusion.DiffusionConfig(step_size=0.05, aggregator="mm_pallas",
                                    byzantine=byz)
    _, h = diffusion.run_diffusion(
        grad_fn=prob.grad_fn(), combination=comb, config=cfg,
        w_star=prob.w_star, num_iters=400, key=jax.random.key(0))
    assert float(np.asarray(h)[-60:].mean()) < 5e-2


def test_kernel_in_diffusion_loop():
    """REF-Diffusion driven by the Pallas kernel reproduces the jnp
    trajectory (same estimator, same numerics)."""
    import jax
    from repro.core import attacks, diffusion, graph
    from repro.data import synthetic

    prob = synthetic.LinearModelProblem(dim=6)
    comb = graph.uniform_weights(graph.fully_connected(8))
    byz = attacks.ByzantineConfig(num_malicious=1, attack="additive",
                                  attack_kwargs=(("delta", 100.0),))
    hists = {}
    for agg in ("mm_tukey", "mm_pallas"):
        cfg = diffusion.DiffusionConfig(step_size=0.05, aggregator=agg,
                                        byzantine=byz)
        _, h = diffusion.run_diffusion(
            grad_fn=prob.grad_fn(), combination=comb, config=cfg,
            w_star=prob.w_star, num_iters=300, key=jax.random.key(0))
        hists[agg] = np.asarray(h)
    # trajectories differ slightly (weighted path uses the lower weighted
    # median as init, the kernel the midpoint median for even K) but both
    # converge robustly to the same steady state
    s_jnp = hists["mm_tukey"][-60:].mean()
    s_ker = hists["mm_pallas"][-60:].mean()
    assert s_ker < 1e-2 and s_jnp < 1e-2
    np.testing.assert_allclose(s_ker, s_jnp, rtol=0.5)
