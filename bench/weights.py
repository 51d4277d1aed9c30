"""Inputs made from ``--seed``: weights and token rows, on the device.

The driver and the plain reference both call these, so both start from
the same numbers without the reference taking anything the program made.
A leaf is named by its path in the parameter tree (``blocks/attn/wq``)
and drawn from a key folded from the seed and that name.
"""

from __future__ import annotations

import functools
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def base_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, also one over 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _leaf(key, path: str, shape: Tuple[int, ...], dtype) -> jax.Array:
    name = path.rsplit("/", 1)[-1]
    if name.startswith("ln") or name.endswith("norm"):
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    scale = 0.02 if name == "embed" else shape[-2] ** -0.5
    return (scale * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def make_params(seed: int, shapes: Dict[str, Tuple[Tuple[int, ...], str]]
                ) -> Dict[str, jax.Array]:
    """{path: array} for {path: (shape, dtype)}, in one jitted call."""
    items = tuple(sorted((p, tuple(s), str(d)) for p, (s, d) in shapes.items()))

    return _gen(base_key(seed), items=items)


@functools.partial(jax.jit, static_argnames=("items",))
def _gen(key, *, items):
    return {p: _leaf(key, p, s, jnp.dtype(d)) for p, s, d in items}


@functools.partial(jax.jit, static_argnames=("rows", "cols", "vocab"))
def _tokens(key, step, *, rows: int, cols: int, vocab: int):
    k = jax.random.fold_in(jax.random.fold_in(key, 0x7E57), step)
    return jax.random.randint(k, (rows, cols), 0, vocab, jnp.int32)


def token_fn(seed: int, rows: int, cols: int, vocab: int):
    """``f(step) -> (rows, cols) int32`` token ids uniform in [0, vocab):
    every step's rows differ, and the same seed gives the same rows."""
    key = base_key(seed)
    return lambda step: _tokens(key, step, rows=rows, cols=cols, vocab=vocab)
