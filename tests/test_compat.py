"""Direct coverage of the ``repro.compat`` helpers: the compile-cache
switch, the compile clock and the thin wrappers over the jax sharding
API, checked against the installed jax line (stubbing ``jax`` entry
points where a call's arguments are what is under test)."""

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat


# ===========================================================================
# the installed API
# ===========================================================================

def test_flags_reflect_the_resident_api():
    # the helpers call these directly: no version branch remains
    assert callable(jax.shard_map)
    assert jax.sharding.AxisType.Auto is not None
    assert callable(jax.sharding.get_abstract_mesh)
    assert not any(name.startswith(("HAS_", "SUPPORTS_"))
                   for name in vars(compat))


# ===========================================================================
# persistent compilation cache
# ===========================================================================

@pytest.fixture
def restore_cache_config():
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    old = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in old.items():
        jax.config.update(n, v)


def test_compilation_cache_disabled_when_env_unset(monkeypatch,
                                                   restore_cache_config):
    # unset env -> the fixed in-checkout directory, never a temp path
    monkeypatch.delenv(compat.COMPILATION_CACHE_ENV, raising=False)
    import pathlib
    repo = pathlib.Path(compat.__file__).resolve().parents[2]
    want = str(repo / ".jax_compile_cache")
    assert compat.DEFAULT_COMPILATION_CACHE_DIR == want
    assert compat.enable_persistent_compilation_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert ".jax_compile_cache/" in (repo / ".gitignore").read_text()


def test_compilation_cache_points_jax_at_the_env_dir(tmp_path, monkeypatch,
                                                     restore_cache_config):
    monkeypatch.setenv(compat.COMPILATION_CACHE_ENV, str(tmp_path))
    assert compat.enable_persistent_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


# ===========================================================================
# make_mesh
# ===========================================================================

class _Recorder:
    def __init__(self, result=None):
        self.calls = []
        self.result = result

    def __call__(self, *args, **kwargs):
        self.calls.append((args, kwargs))
        return self.result


def test_make_mesh_modern_requests_all_auto_axes(monkeypatch):
    rec = _Recorder(result="mesh")
    monkeypatch.setattr(jax, "make_mesh", rec)
    assert compat.make_mesh((1, 1), ("agents", "model")) == "mesh"
    ((args, kwargs),) = rec.calls
    assert args == ((1, 1), ("agents", "model"))
    assert kwargs["axis_types"] == (jax.sharding.AxisType.Auto,) * 2


def test_make_mesh_live_branch_builds_a_real_mesh():
    mesh = compat.make_mesh((1,), ("agents",))
    assert mesh.shape == {"agents": 1}


# ===========================================================================
# get_abstract_mesh
# ===========================================================================

def test_abstract_mesh_modern_branch(monkeypatch):
    class _FakeMesh:
        def __init__(self, shape):
            self.shape = shape

    monkeypatch.setattr(jax.sharding, "get_abstract_mesh",
                        lambda: _FakeMesh({}))
    assert compat.get_abstract_mesh() is None      # empty mesh -> None

    full = _FakeMesh({"agents": 2})
    monkeypatch.setattr(jax.sharding, "get_abstract_mesh", lambda: full)
    assert compat.get_abstract_mesh() is full


def test_abstract_mesh_tracks_the_active_mesh():
    assert compat.get_abstract_mesh() is None      # no active mesh
    mesh = compat.make_mesh((1,), ("agents",))
    with jax.set_mesh(mesh):
        got = compat.get_abstract_mesh()
        assert got is not None and dict(got.shape) == {"agents": 1}
    assert compat.get_abstract_mesh() is None


# ===========================================================================
# shard_map
# ===========================================================================

def test_shard_map_modern_kwarg_translation(monkeypatch):
    rec = _Recorder(result="wrapped")
    monkeypatch.setattr(jax, "shard_map", rec)

    fn = lambda x: x  # noqa: E731
    assert compat.shard_map(fn, in_specs="i", out_specs="o",
                            axis_names=("agents",)) == "wrapped"
    ((args, kwargs),) = rec.calls
    assert args == (fn,)
    assert kwargs == {"in_specs": "i", "out_specs": "o",
                      "check_vma": False, "axis_names": {"agents"}}

    rec.calls.clear()
    compat.shard_map(fn, mesh="m", in_specs="i", out_specs="o",
                     check_vma=True)
    ((_, kwargs),) = rec.calls
    assert kwargs["mesh"] == "m" and kwargs["check_vma"] is True
    assert "axis_names" not in kwargs


def test_shard_map_executes_on_a_real_mesh():
    P = jax.sharding.PartitionSpec
    mesh = compat.make_mesh((1,), ("agents",))
    wrapped = compat.shard_map(lambda x: x * 2, mesh=mesh,
                               in_specs=P("agents"), out_specs=P("agents"))
    out = wrapped(jnp.arange(4, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(out), [0.0, 2.0, 4.0, 6.0])


# ===========================================================================
# compile_clock
# ===========================================================================

@pytest.mark.parametrize("depth", [1, 2, 3])
def test_compile_clock_every_nested_scope_sees_the_compile(depth):
    f = jax.jit(lambda x: jnp.cos(x) * depth)     # fresh: never compiled
    x = jnp.ones(5)
    with contextlib.ExitStack() as stack:
        clocks = [stack.enter_context(compat.compile_clock())
                  for _ in range(depth)]
        t0 = time.perf_counter()
        f(x).block_until_ready()
        wall = time.perf_counter() - t0
    for c in clocks:
        assert c["compiles"] >= 1 and c["trace_s"] > 0
        assert c["cache_hits"] >= 0
        # no interval counted twice: the parts fit in the wall time
        assert c["trace_s"] + c["lower_s"] + c["compile_s"] <= wall
        assert c == clocks[0]
    with compat.compile_clock() as again:
        f(x).block_until_ready()
    assert again == {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
                     "compiles": 0, "cache_hits": 0}


def test_compile_clock_scope_is_removed_by_identity():
    with compat.compile_clock():
        twin = compat._ACTIVE_CLOCKS[-1]
        with compat.compile_clock():
            # both scopes hold equal (empty) event lists here
            assert compat._ACTIVE_CLOCKS[-1] == twin
        assert compat._ACTIVE_CLOCKS[-1] is twin
        assert len([c for c in compat._ACTIVE_CLOCKS if c is twin]) == 1
    assert not any(c is twin for c in compat._ACTIVE_CLOCKS)


def test_compile_clock_splits_nested_traces_without_double_counting():
    # spans: trace [0, 4] holds a nested trace [1, 2]; lower [3, 6]
    # overlaps the trace; compile [6, 9]
    events = [("trace_s", 1.0, 2.0), ("trace_s", 0.0, 4.0),
              ("lower_s", 3.0, 6.0), ("compile_s", 6.0, 9.0)]
    with compat.compile_clock() as c:
        compat._ACTIVE_CLOCKS[-1].extend(events)
    assert c["trace_s"] == 4.0 and c["lower_s"] == 2.0
    assert c["compile_s"] == 3.0 and c["compiles"] == 1


def test_compile_totals_count_each_function_by_its_name():
    def totals_probe_fn(x):                 # a name no other test uses
        return jnp.sin(x) + inner(x)

    @jax.jit
    def totals_probe_inner(x):
        return x * 2.0
    inner = totals_probe_inner
    zeros = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0, "compiles": 0}
    assert compat.compile_totals("totals_probe_fn") == zeros
    f = jax.jit(totals_probe_fn)
    f.lower(jnp.ones(3)).compile()
    once = compat.compile_totals("totals_probe_fn")
    assert once["compiles"] == 1
    assert min(once["trace_s"], once["lower_s"], once["compile_s"]) > 0
    # the jit it calls is traced inside it, and never compiled alone
    inner_t = compat.compile_totals("totals_probe_inner")
    assert 0 < inner_t["trace_s"] <= once["trace_s"]
    assert inner_t["compiles"] == 0
    f(jnp.ones(3)).block_until_ready()      # the same shapes: no compile
    assert compat.compile_totals("totals_probe_fn")["compiles"] == 1
    f(jnp.ones(4)).block_until_ready()      # new shapes: one more
    assert compat.compile_totals("totals_probe_fn")["compiles"] == 2
    # a copy: the caller cannot change the totals
    compat.compile_totals("totals_probe_fn")["compiles"] = 99
    assert compat.compile_totals("totals_probe_fn")["compiles"] == 2
