"""Bitonic sort networks (kernels.mm_aggregate) vs jnp.sort / stable
argsort on adversarial patterns.

The kernel's medians and cumulative-weight crossings are computed from
these networks, so the contract is:
  * plain sort == jnp.sort exactly, including ties, +/-inf sentinel
    rows, constant tiles and pre-/reverse-sorted inputs;
  * the paired variant permutes every carried plane with the per-column
    value order -- exactly equal to a stable argsort gather on distinct
    values, and equal on every *derived order statistic* under ties
    (tied values are interchangeable, so the weighted median crossing
    must agree even when the tie-internal permutation differs).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import location
from repro.kernels import mm_aggregate as K
from repro.kernels import ref


def _adversarial(name: str, p: int, m: int) -> jnp.ndarray:
    key = jax.random.key(hash(name) % (2 ** 31))
    x = jax.random.normal(key, (p, m))
    if name == "ties":
        x = jnp.round(x * 2) / 2            # heavy duplicate values
    elif name == "pos_inf_rows":
        x = x.at[-max(1, p // 4):].set(jnp.inf)   # kernel K-pad sentinels
    elif name == "neg_inf_rows":
        x = x.at[: max(1, p // 4)].set(-jnp.inf)
    elif name == "mixed_inf":
        x = x.at[0].set(jnp.inf).at[-1].set(-jnp.inf)
    elif name == "constant":
        x = jnp.zeros((p, m))
    elif name == "presorted":
        x = jnp.sort(x, axis=0)
    elif name == "reversed":
        x = jnp.sort(x, axis=0)[::-1]
    return x


PATTERNS = ("random", "ties", "pos_inf_rows", "neg_inf_rows", "mixed_inf",
            "constant", "presorted", "reversed")


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("p", [2, 4, 8, 32, 64])
def test_plain_bitonic_matches_jnp_sort(pattern, p):
    x = _adversarial(pattern, p, 23)
    got, _ = K._bitonic_sort_rows(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(jnp.sort(x, axis=0)),
                                  err_msg=f"{pattern} p={p}")


def test_next_pow2():
    assert [K.next_pow2(n) for n in (1, 2, 3, 4, 5, 33, 64)] == \
        [2, 2, 4, 4, 8, 64, 64]


@pytest.mark.parametrize("p", [4, 16, 64])
def test_paired_bitonic_matches_stable_argsort_distinct(p):
    """On distinct values the carried planes must equal the stable
    argsort gather exactly (there is a unique sort permutation)."""
    x = jax.random.permutation(
        jax.random.key(p), jnp.arange(p * 11, dtype=jnp.float32)
    ).reshape(p, 11)
    w = jax.random.uniform(jax.random.key(p + 1), (p, 3, 11))
    xs, (ws,) = K._bitonic_sort_rows(x, (w,))
    want_x, want_w = ref.paired_sort_ref(x, w)
    np.testing.assert_array_equal(np.asarray(xs), np.asarray(want_x))
    np.testing.assert_array_equal(np.asarray(ws), np.asarray(want_w))


@pytest.mark.parametrize("pattern", ("ties", "mixed_inf", "constant"))
def test_paired_bitonic_weighted_median_under_ties(pattern):
    """Under ties the tie-internal permutation may differ from stable
    argsort, but the weighted-median crossing must match the oracle."""
    p = 16
    x = _adversarial(pattern, p, 19)
    # make ±inf rows weight-0 sentinels, as the kernel does
    finite = jnp.isfinite(x)
    a = jax.random.uniform(jax.random.key(3), (p, 4), minval=0.05, maxval=1.0)
    for n in range(4):
        col = jnp.where(finite.all(axis=1), a[:, n], 0.0)
        col = col / jnp.sum(col) if float(jnp.sum(col)) > 0 else \
            jnp.full((p,), 1.0 / p)
        planes = jnp.broadcast_to(col[:, None, None], (p, 1, x.shape[1]))
        xv = jnp.where(finite, x, jnp.inf)   # sentinel convention
        xs, (ws,) = K._bitonic_sort_rows(xv, (planes,))
        got = K._wquantile_planes(xs, ws, 0.5)[0]
        want = location.weighted_median(
            jnp.where(finite, x, 0.0) if not bool(finite.all()) else x, col)
        if bool(finite.all()):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-6, err_msg=f"{pattern} n={n}")
        else:
            # sentinel rows carry zero weight: crossing stays finite
            assert bool(jnp.isfinite(got).all()), pattern


@pytest.mark.parametrize("k", [3, 5, 33])
def test_odd_k_pads_through_network(k):
    """Odd/non-pow2 K flows through the register top-up: kernel output
    still matches the oracle (the end-to-end tie-in for the network)."""
    x = jax.random.normal(jax.random.key(k), (k, 77))
    x = x.at[-1:].add(100.0)
    got = K.mm_aggregate_2d(x, interpret=True)
    np.testing.assert_allclose(got, ref.mm_aggregate_ref(x), atol=1e-5)


def _count_compare_passes(p: int) -> int:
    """Compare-exchange passes actually traced by the network: each pass
    makes exactly one row-pair `gt` comparison, so count `gt` equations
    in the jaxpr of _bitonic_sort_rows."""
    x = jnp.zeros((p, 8))
    jaxpr = jax.make_jaxpr(lambda v: K._bitonic_sort_rows(v)[0])(x).jaxpr
    return sum(1 for eqn in jaxpr.eqns if eqn.primitive.name == "gt")


def test_compare_exchange_count_is_subquadratic():
    """The traced network does log2(K)*(log2(K)+1)/2 compare-exchange
    passes (O(K log^2 K) work); guard the pass structure so a
    regression to the O(K^2) odd-even network (K passes) is caught."""
    for p, stages in ((2, 1), (4, 3), (8, 6), (64, 21)):
        got = _count_compare_passes(p)
        assert got == stages, (p, got)
        assert got < p or p == 2   # strictly fewer passes than odd-even


# ---------------------------------------------------------------------------
# the single-pass kernel's network over a list of agent slabs
# ---------------------------------------------------------------------------

def _slabs(x):
    return [x[i] for i in range(x.shape[0])]


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("k", [2, 3, 4, 5, 10, 33])
def test_slab_network_matches_jnp_sort(pattern, k):
    """The slab network equals jnp.sort for every agent count, the
    power-of-two top-up existing only as trace-time sentinels, which
    end up last."""
    x = _adversarial(pattern, k, 23).reshape(k, 1, 23)
    got, _ = K._sort_slabs(_slabs(x))
    p = K.next_pow2(k)
    assert len(got) == p and all(g is None for g in got[k:])
    np.testing.assert_array_equal(np.stack([np.asarray(g) for g in got[:k]]),
                                  np.asarray(jnp.sort(x, axis=0)),
                                  err_msg=f"{pattern} k={k}")


@pytest.mark.parametrize("k", [3, 4, 16, 33])
def test_slab_network_carries_follow_stable_argsort_distinct(k):
    x = jax.random.permutation(
        jax.random.key(k), jnp.arange(k * 11, dtype=jnp.float32)
    ).reshape(k, 1, 11)
    w = jax.random.uniform(jax.random.key(k + 1), (k, 1, 11))
    xs, (ws,) = K._sort_slabs(_slabs(x), (_slabs(w),))
    want_x, want_w = ref.paired_sort_ref(x[:, 0], w[:, 0])
    np.testing.assert_array_equal(np.stack(xs[:k])[:, 0], np.asarray(want_x))
    np.testing.assert_array_equal(np.stack(ws[:k])[:, 0], np.asarray(want_w))


@pytest.mark.parametrize("pattern", ("random", "ties", "constant"))
@pytest.mark.parametrize("k", [5, 16])
def test_slab_weighted_median_matches_oracle(pattern, k):
    x = _adversarial(pattern, k, 19)
    col = jax.random.uniform(jax.random.key(k), (k,), minval=0.05, maxval=1.0)
    col = col / jnp.sum(col)
    planes = [jnp.full((19,), c) for c in col]
    xs, (ws,) = K._sort_slabs(_slabs(x), (planes,))
    got = K._weighted_median_slabs(xs, ws)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(location.weighted_median(x, col)),
                               atol=1e-6, err_msg=pattern)
