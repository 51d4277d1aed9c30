"""Distributed collectives: sharded robust all-reduce == unsharded ref.

Runs on 8 forced host devices in a subprocess (jax device count locks at
first init, and the main test process must keep seeing 1 device)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.core import aggregators, sharded

    mesh = compat.make_mesh((8,), ("data",))
    x = jax.random.normal(jax.random.key(0), (8, 1037))
    x = x.at[-2:].add(500.0)
    ref = aggregators.mm_tukey(x, None)
    mean_ref = jnp.mean(x, axis=0)

    def run(method):
        f = compat.shard_map(
            lambda v: sharded.robust_all_reduce(v[0], "data", method=method),
            mesh=mesh, in_specs=P("data", None), out_specs=P(None),
            check_vma=False)
        return jax.jit(f)(x)

    out = {}
    out["gather_mm"] = float(jnp.max(jnp.abs(run("gather_mm") - ref)))
    out["rs_mm"] = float(jnp.max(jnp.abs(run("rs_mm") - ref)))
    out["mean"] = float(jnp.max(jnp.abs(run("mean") - mean_ref)))

    # dim0-preserving rs path (2D leaf): distinct per-agent values
    stacks = jax.random.normal(jax.random.key(2), (8, 16, 24))
    ref2 = aggregators.mm_tukey(stacks, None)
    got2 = jax.jit(compat.shard_map(
        lambda v: sharded.rs_mm(v[0], "data"),
        mesh=mesh, in_specs=P("data", None, None), out_specs=P(None),
        check_vma=False))(stacks)
    out["rs_mm_dim0"] = float(jnp.max(jnp.abs(got2 - ref2)))

    # tree version
    tree = {"w": jax.random.normal(jax.random.key(3), (8, 32, 6)),
            "b": jax.random.normal(jax.random.key(4), (8, 11))}
    reft = {k: aggregators.mm_tukey(v, None) for k, v in tree.items()}
    gott = jax.jit(compat.shard_map(
        lambda t: sharded.robust_all_reduce_tree(
            {k: v[0] for k, v in t.items()}, "data", method="rs_mm"),
        mesh=mesh,
        in_specs=({"w": P("data", None, None), "b": P("data", None)},),
        out_specs={"w": P(None), "b": P(None)}, check_vma=False))(tree)
    out["tree"] = max(float(jnp.max(jnp.abs(gott[k] - reft[k])))
                      for k in tree)
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"      # a child never competes for a chip
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_gather_mm_matches_ref(results):
    assert results["gather_mm"] < 1e-5


def test_rs_mm_matches_ref(results):
    assert results["rs_mm"] < 1e-5


def test_rs_mm_dim0_matches_ref(results):
    assert results["rs_mm_dim0"] < 1e-5


def test_mean_matches(results):
    assert results["mean"] < 1e-5


def test_tree_matches(results):
    assert results["tree"] < 1e-5
