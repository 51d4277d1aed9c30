"""Logical-axis sharding rules (MaxText-style) with divisibility fallback.

Model code annotates activations/params with *logical* axis names via
``shard(x, "batch", "seq", "heads", None)``.  A rules table maps logical
names to mesh axes; a name whose dimension does not divide the mapped
mesh axes is silently replicated (e.g. kv_heads=8 on model=16).

Outside an active mesh context ``shard`` is the identity, so all model
code runs unchanged on a bare CPU device.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat

# logical name -> tuple of mesh axis names (tried jointly, then prefixes)
DEFAULT_RULES: dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "fsdp": ("pod", "data"),     # param dim sharded ZeRO-style over data ranks
    "seq": (),
    "embed": (),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": ("model",),      # used when kv_heads doesn't divide
    "mlp": ("model",),
    "experts": ("model",),
    "vocab": ("model",),
    "cache_heads": ("model",),
}


class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: Optional[dict] = None


_CTX = _Ctx()


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Optional[dict] = None):
    """Activate logical sharding (and the jax mesh context) for a region."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh = mesh
    _CTX.rules = dict(DEFAULT_RULES, **(rules or {}))
    try:
        with mesh:
            yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def active_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def _axes_for(name: Optional[str], dim: int, mesh: Mesh, rules: dict):
    """Resolve a logical name to mesh axes, dropping axes that don't divide."""
    if name is None:
        return None
    want = rules.get(name, ())
    # only axes present in this mesh (and bigger than 1 -- sharding over a
    # singleton axis is a no-op that just clutters the spec)
    want = tuple(a for a in want if mesh.shape.get(a, 1) > 1)
    if not want:
        return None
    # try the full product, then shrink from the right until it fits.
    # Uneven sharding is allowed for large dims (>= 8x the axis product):
    # GSPMD pads the last shard -- this is how non-divisible vocabularies
    # (e.g. seamless 256206 on 16-way model parallelism) stay sharded
    # instead of replicating multi-GiB logits.
    axes = list(want)
    while axes:
        prod = 1
        for a in axes:
            prod *= mesh.shape[a]
        if dim % prod == 0 or dim >= 8 * prod:
            return tuple(axes) if len(axes) > 1 else axes[0]
        axes.pop()
    return None


def logical_spec(names: Sequence[Optional[str]], shape: Sequence[int],
                 mesh: Mesh, rules: Optional[dict] = None) -> P:
    rules = dict(DEFAULT_RULES, **(rules or {}))
    used: set = set()
    entries = []
    for name, dim in zip(names, shape):
        ax = _axes_for(name, dim, mesh, rules)
        # one mesh axis may shard only one dim
        if ax is not None:
            flat = ax if isinstance(ax, tuple) else (ax,)
            if any(a in used for a in flat):
                ax = None
            else:
                used.update(flat)
        entries.append(ax)
    return P(*entries)


def shard(x, *names: Optional[str]):
    """Apply a logical sharding constraint (identity outside a mesh ctx)."""
    mesh, rules = _CTX.mesh, _CTX.rules
    if mesh is None:
        return x
    if len(names) != x.ndim:
        raise ValueError(f"{len(names)} names for rank-{x.ndim} array")
    spec = logical_spec(names, x.shape, mesh, rules)
    # Inside jit/shard_map the constraint must be built against the
    # *abstract* context mesh (whose axis_types reflect Manual regions);
    # the concrete mesh is only used for shape/divisibility decisions.
    am = compat.get_abstract_mesh()
    target = am if am is not None else mesh
    return jax.lax.with_sharding_constraint(x, NamedSharding(target, spec))


def named_sharding(mesh: Mesh, *names: Optional[str], shape=None) -> NamedSharding:
    if shape is None:
        raise ValueError("shape required for divisibility-aware specs")
    return NamedSharding(mesh, logical_spec(names, shape, mesh, None))
