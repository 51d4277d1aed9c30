"""The MM location estimate along axis 0, written from the paper's
description: (weighted) median and MAD for the start, then ``iters``
Tukey-biweight IRLS steps standardised by that MAD (Maronna, Martin and
Yohai, Robust Statistics, 2006, sec. 5.4).

  start   mu0 = median (mean of the middle pair when K is even), or the
          smallest value whose normalised cumulative weight reaches 1/2
  scale   s = max(1.4826 * median |x - mu0|, 1e-12)
  step    b = (1 - (r/c)^2)^2 for |r| < c, else 0, with r = (x - mu)/s;
          mu <- sum(a b x) / sum(a b), kept where sum(a b) <= 1e-12
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

TUKEY_C = 4.685
MAD_SCALE = 1.4826022185056018
FLOOR = 1e-12
CHUNK = 1 << 22


def _sorted_rows(rows, carry=None):
    """Odd-even transposition sort of K rows, coordinate by coordinate
    (K passes of compare-exchange; a pair swaps only when strictly out of
    order, so equal values keep their order).  ``carry`` rows move with
    their values."""
    rows = list(rows)
    carry = None if carry is None else list(carry)
    k = len(rows)
    for p in range(k):
        for i in range(p % 2, k - 1, 2):
            swap = rows[i] > rows[i + 1]
            rows[i], rows[i + 1] = (jnp.where(swap, rows[i + 1], rows[i]),
                                    jnp.where(swap, rows[i], rows[i + 1]))
            if carry is not None:
                carry[i], carry[i + 1] = (
                    jnp.where(swap, carry[i + 1], carry[i]),
                    jnp.where(swap, carry[i], carry[i + 1]))
    return rows, carry


def _median0(x):
    k = x.shape[0]
    xs, _ = _sorted_rows([x[i] for i in range(k)])
    return 0.5 * (xs[(k - 1) // 2] + xs[k // 2])


def _wmedian0(x, a):
    k = x.shape[0]
    xs, ws = _sorted_rows([x[i] for i in range(k)],
                          [jnp.broadcast_to(a[i], x.shape[1:])
                           for i in range(k)])
    cw = jnp.zeros(x.shape[1:], x.dtype)
    out = xs[-1]
    found = jnp.zeros(x.shape[1:], bool)
    for v, w in zip(xs, ws):
        cw = cw + w
        hit = (cw >= 0.5 - 1e-12) & ~found
        out = jnp.where(hit, v, out)
        found = found | hit
    return out


@functools.partial(jax.jit, static_argnames=("iters", "c", "weighted"))
def _mm_chunk(x, a, *, iters: int, c: float, weighted: bool):
    x = x.astype(jnp.float32)
    k = x.shape[0]
    if weighted:
        a = a.astype(jnp.float32)
        ok = jnp.all(jnp.isfinite(a) & (a >= 0)) & (jnp.sum(a) > FLOOR)
        a = jnp.where(ok, a / jnp.where(ok, jnp.sum(a), 1.0), 1.0 / k)
        mu = _wmedian0(x, a)
    else:
        a = jnp.full((k,), 1.0 / k, jnp.float32)
        mu = _median0(x)
    s = jnp.maximum(MAD_SCALE * _median0(jnp.abs(x - mu[None])), FLOOR)
    ac = a[:, None]
    for _ in range(iters):
        r = (x - mu[None]) / s[None]
        u = jnp.clip(1.0 - (r * r) / (c * c), 0.0, 1.0)
        b = u * u
        num = jnp.sum(ac * b * x, axis=0)
        den = jnp.sum(ac * b, axis=0)
        mu = jnp.where(den > FLOOR, num / jnp.where(den > FLOOR, den, 1.0), mu)
    return mu


@functools.partial(jax.jit, static_argnames=("nmal", "size"))
def _stack(flats, start, delta, *, nmal: int, size: int):
    x = jnp.stack([jax.lax.dynamic_slice_in_dim(f, start, size) for f in flats])
    if nmal:
        x = x.at[x.shape[0] - nmal:].add(delta)
    return x


def aggregate(per_agent, *, method: str = "mm", a=None, nmal: int = 0,
              delta: float = 0.0, iters: int = 10, c: float = TUKEY_C,
              chunk: int = CHUNK):
    """Aggregate K same-shaped arrays over the agents, coordinate by
    coordinate, after shifting the last ``nmal`` by ``delta``: the MM
    estimate or the mean.  Works through ``chunk`` coordinates at a time,
    so a vocabulary-sized leaf needs its K inputs, the output and one
    chunk's temporaries."""
    shape = per_agent[0].shape
    flat = [x.reshape(-1) for x in per_agent]
    n = flat[0].shape[0]
    weighted = a is not None
    a = jnp.ones((len(flat),), jnp.float32) if a is None else jnp.asarray(a)
    size = min(chunk, n)
    out = []
    for i in range(0, n, size):
        start = min(i, n - size)        # the last chunk ends at n
        x = _stack(flat, start, delta, nmal=nmal, size=size)
        if method == "mean":
            r = jnp.mean(x, axis=0)
        else:
            r = _mm_chunk(x, a, iters=iters, c=c, weighted=weighted)
        out.append(r if start == i else r[i - start:])
        del x
    return (out[0] if len(out) == 1 else jnp.concatenate(out)).reshape(shape)


def mm_estimate(x, a=None, *, iters: int = 10, c: float = TUKEY_C,
                chunk: int = CHUNK):
    """(K, ...) -> (...)."""
    return aggregate(list(x), a=a, iters=iters, c=c, chunk=chunk)
