"""Plain reference of a buffered asynchronous aggregation server's
commits, written from the service's documented policy (FedBuff,
Nguyen et al., arXiv:2106.06639, with the robust MM estimate in place of
the mean, and the repo's docs/serving.md for the weighting, the health
gate and the trust region).

Given the cohorts the server committed, in order (which updates each
took), it recomputes every commit:

  weight   a_j = w_client * (1 + s_j)^-alpha * (floor + (1 - floor) h_j),
           s_j = commits so far - the update's round tag, h_j the
           client's health score (starts at 1)
  estimate the weighted MM estimate of the cohort rows (bench/reference/mm.py);
           a cohort under k_min (a deadline admission) is padded with
           rows of the current model at half the cohort's mass each and
           estimated with c scaled by ``degraded_c_scale``
  health   a member whose distance to the estimate exceeds
           median + z * max(1.4826 MAD, max(1e-7, 1e-3 max(median, 1)))
           of the members' distances is an outlier: h <- (1 - beta) h;
           every other member: h <- (1 - beta) h + beta
  clip     the step estimate - model is cut to trust_factor x the running
           mean of past full-cohort step norms (0.9 old + 0.1 new, from
           the first full cohort's norm), then the model moves

``dtype="bfloat16"`` is the control: rows, weights and the estimate in
bfloat16 throughout.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import mm

MAD_SCALE = 1.4826


@dataclasses.dataclass(frozen=True)
class Member:
    client: int
    pool_index: int          # row of the seed's payload pool
    weight: float            # client weight
    round_tag: int           # server round the update was computed from


class Server:
    """Replays committed cohorts from ``model0``."""

    def __init__(self, policy: dict, model0, pool, *, dtype="float32"):
        self.p = policy
        self.dtype = jnp.dtype(dtype)
        self.pool = pool.astype(self.dtype)
        self.w = jnp.asarray(model0, self.dtype)
        self.round = 0
        self.ema: Optional[float] = None
        self.health: Dict[int, float] = {}

    def _factor(self, m: Member) -> float:
        p = self.p
        s = max(self.round - m.round_tag, 0)
        a = m.weight * (1.0 + s) ** -p["staleness_alpha"]
        if p["health_gate"]:
            h = self.health.get(m.client, 1.0)
            a *= p["health_floor"] + (1.0 - p["health_floor"]) * h
        return a

    def commit(self, members: List[Member]) -> Tuple[tuple, bool]:
        """Apply one commit; returns the members flagged as outliers and
        whether the trust region clipped the step."""
        p = self.p
        k_min = int(p["k_min"])
        x = self.pool[jnp.asarray([m.pool_index for m in members])]
        a = np.asarray([self._factor(m) for m in members], np.float64)
        degraded = len(members) < k_min
        c = mm.TUKEY_C * (p["degraded_c_scale"] if degraded else 1.0)
        rows, wts = x, a
        if degraded:
            n_anchor = k_min - len(members)
            rows = jnp.concatenate(
                [x, jnp.broadcast_to(self.w, (n_anchor,) + self.w.shape)])
            wts = np.concatenate([a, np.full(n_anchor, a.sum() / n_anchor)])
        est = mm.aggregate(list(rows), a=jnp.asarray(wts, self.dtype),
                           iters=int(p["num_iters"]), c=c)
        est = est.astype(self.dtype)
        outliers = self._health(x, members, est) if p["health_gate"] else ()
        delta = est - self.w
        norm = float(jnp.sqrt(jnp.sum(jnp.square(delta.astype(jnp.float32)))))
        clipped = False
        if self.ema is not None:
            cap = p["trust_factor"] * self.ema
            if norm > cap > 0.0:
                est = self.w + delta * jnp.asarray(cap / norm, self.dtype)
                norm, clipped = cap, True
        if not degraded:
            self.ema = norm if self.ema is None else 0.9 * self.ema + 0.1 * norm
        self.w = est
        self.round += 1
        return outliers, clipped

    def _health(self, x, members, est) -> tuple:
        p = self.p
        r = np.asarray(jnp.sqrt(jnp.sum(jnp.square(
            (x - est[None]).astype(jnp.float32)), axis=1)), np.float64)
        med = float(np.median(r))
        madn = MAD_SCALE * float(np.median(np.abs(r - med)))
        floor = max(1e-7, 1e-3 * max(med, 1.0))
        thresh = med + p["residual_z"] * max(madn, floor)
        out = []
        beta = p["health_alpha"]
        for m, ri in zip(members, r):
            h = self.health.get(m.client, 1.0)
            if ri > thresh:
                out.append(m.client)
                self.health[m.client] = (1.0 - beta) * h
            else:
                self.health[m.client] = (1.0 - beta) * h + beta
        return tuple(out)

    def model(self) -> np.ndarray:
        return np.asarray(self.w.astype(jnp.float32))


def make_pool(key, dim: int, n_honest: int, n_attack: int, scale: float,
              sd: float, shift_sd: float):
    """(n_honest + n_attack, dim) float32 payloads and the optimum: honest
    rows are the optimum plus N(0, sd^2) noise; attack rows are honest
    rows plus ``shift_sd`` x sd on every coordinate."""
    k_opt, k_noise = jax.random.split(key)
    opt = scale * jax.random.normal(k_opt, (dim,), jnp.float32)
    honest = opt[None] + sd * jax.random.normal(k_noise, (n_honest, dim),
                                                jnp.float32)
    attack = honest[:n_attack] + shift_sd * sd
    return jnp.concatenate([honest, attack]), opt


make_pool_jit = jax.jit(make_pool, static_argnames=(
    "dim", "n_honest", "n_attack", "scale", "sd", "shift_sd"))
