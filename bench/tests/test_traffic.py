"""The serve cell's traffic: deterministic for a seed, the same counts
and gaps on every seed, and the attackers' share exact."""

import numpy as np

from bench.drivers import serve
from bench.tests.conftest import SERVE_TRAFFIC


def _traffic():
    return dict(SERVE_TRAFFIC)


def test_same_seed_same_schedule():
    a, wa = serve.schedule(_traffic(), 2 ** 31 + 7, 20.0, 60.0)
    b, wb = serve.schedule(_traffic(), 2 ** 31 + 7, 20.0, 60.0)
    for x, y in zip((a.t, a.client, a.pool, a.stale), (b.t, b.client, b.pool,
                                                      b.stale)):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(wa, wb)


def test_seeds_reorder_the_same_work():
    tr = _traffic()
    a, _ = serve.schedule(tr, 1, 20.0, 60.0)
    b, _ = serve.schedule(tr, 2, 20.0, 60.0)
    assert a.n_window == b.n_window == round(tr["rate_per_s"] * 20.0)
    # the same gaps in another order (one of them, the first, is dropped)
    ga, gb = np.sort(np.diff(a.t[:a.n_window])), np.sort(np.diff(b.t[:b.n_window]))
    q = [0.1, 0.5, 0.9]
    np.testing.assert_allclose(np.quantile(ga, q), np.quantile(gb, q),
                               rtol=0.02)
    assert not np.array_equal(a.client, b.client)
    assert np.all(a.t[:a.n_window] < 20.0) and np.all(a.t[a.n_window:] >= 20.0)


def test_attackers_send_ten_percent_on_every_seed():
    tr = _traffic()
    ranks = np.asarray(tr["attacker_ranks"]) - 1
    for seed in (0, 5, 2 ** 31 + 3):
        s, _ = serve.schedule(tr, seed, 20.0, 60.0)
        w = slice(0, s.n_window)
        assert s.attack[w].sum() == round(0.1 * s.n_window)
        assert np.array_equal(np.isin(s.client[w], ranks), s.attack[w])
        assert np.all(s.pool[w][s.attack[w]] >= tr["pool_honest"])
        assert np.all(s.stale[w] <= tr["staleness_max"])
