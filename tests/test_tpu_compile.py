"""The Pallas kernels compiled for a described TPU v5e chip.

Nothing here runs on a chip: the TPU compiler, which ships with jaxlib,
compiles for a ``v5e:2x2`` topology that is described, not attached, so
every case raises what the chip's compiler would raise (unsupported
primitives, unaligned blocks, VMEM overflow).  Each case goes through
``ops.AggregationEngine`` -- the entry point every caller uses -- with
interpret mode off, and asserts that the compiled program holds a Mosaic
kernel (``tpu_custom_call``) and fits one chip's HBM.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and test collection must be the same
in every worker.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import mm_aggregate, ops, tuning

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    # libtpu otherwise writes its logs to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _compile(sharding, k, m, n=None, *, dtype=jnp.float32, weighted=False,
             block_m=None, block_k=None):
    """Compile one engine launch for the described chip; returns the
    compiled program and the workload the engine resolved."""
    eng = ops.AggregationEngine(interpret=False, block_m=block_m,
                                block_k=block_k)
    x = jax.ShapeDtypeStruct((k, m), dtype, sharding=sharding)
    if n is not None:
        a = jax.ShapeDtypeStruct((k, n), jnp.float32, sharding=sharding)
        fn, args = eng.aggregate_batched, (x, a)
    elif weighted:
        a = jax.ShapeDtypeStruct((k,), jnp.float32, sharding=sharding)
        fn, args = eng.aggregate, (x, a)
    else:
        fn, args = eng.aggregate, (x,)
    with ops.record_workloads() as rec:
        compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's instruction carries its stable name, as a profile shows
    name = mm_aggregate.KERNEL_NAMES[rec[0]["path"]]
    assert re.search(rf"%{name}(\.\d+)? = \S+ custom-call\(", text), name
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used <= V5E_HBM_BYTES, used
    return compiled, rec[0]


@pytest.mark.parametrize("k,m,dtype,weighted", [
    (8, 2 ** 22, jnp.float32, False),     # chip_smoke kernel phase
    (8, 4096, jnp.float32, False),
    (64, 4096, jnp.float32, False),
    (8, 4096, jnp.bfloat16, False),
    (64, 4096, jnp.bfloat16, False),
    (8, 4096, jnp.float32, True),         # weighted: was a cumsum
    (8, 2 ** 22, jnp.float32, True),      # the serve launch geometry
])
def test_single_pass_compiles_for_v5e(one_chip, k, m, dtype, weighted):
    _, rec = _compile(one_chip, k, m, dtype=dtype, weighted=weighted)
    assert rec["path"] == "single"


@pytest.mark.parametrize("k,m,n", [
    (8, 4096, 8),             # batched: the (P, N, bm) planes aborted Mosaic
    (16, 2 ** 20, 16),        # the diffusion shape of the kernel phase
])
def test_batched_weighted_compiles_for_v5e(one_chip, k, m, n):
    _, rec = _compile(one_chip, k, m, n)
    assert rec["path"] == "single"


@pytest.mark.parametrize("k,m,weighted", [
    (256, 4096, False),
    (256, 4096, True),
    (1024, 4096, False),
    (1024, 4096, True),
    (512, 2 ** 20, False),    # K=1024 clients at participation 0.5
])
def test_two_pass_compiles_for_v5e(one_chip, k, m, weighted):
    _, rec = _compile(one_chip, k, m, weighted=weighted)
    assert rec["path"] == "two_pass"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_split_k_candidate_compiles_for_v5e(one_chip, dtype):
    k, m = 32, 4096
    splits = [c for c in tuning.candidate_choices(k, m, 1, dtype)
              if c.path == "single" and c.block_k is not None]
    assert splits, "candidate_choices offers no K split at K=32"
    c = splits[0]
    _, rec = _compile(one_chip, k, m, dtype=dtype, block_m=c.block_m,
                      block_k=c.block_k)
    assert rec["block_k"] == c.block_k < k
