"""The trace reduction: busy union, kernel and collective time, idle gaps
named by host spans, on a hand-made trace and on one recorded on a v5e
chip (``bench/testdata``)."""

import gzip

import pytest

from bench import trace_reduce
from bench.tests.conftest import BENCH

MS = 1e6


def _trace():
    ops = {0: [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0 * MS, 4 * MS),
               ("%_agg_nd_impl.7 = f32[1,8]{1,0} custom-call(f32[4,8]{1,0} %b), custom_call_target=\"tpu_custom_call\"", 3 * MS, 9 * MS),
               ("%all-to-all.2 = f32[4]{0} all-to-all(f32[4]{0} %q)", 12 * MS, 14 * MS),
               ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %r)", 30 * MS, 50 * MS)],
           1: [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0 * MS, 10 * MS)]}
    spans = [("bench.window", 2 * MS, 42 * MS), ("bench.pump", 14 * MS, 20 * MS),
             ("bench.idle", 20 * MS, 29 * MS)]
    return trace_reduce.Trace(ops=ops, spans=spans, window=(2 * MS, 42 * MS))


def test_busy_is_the_union_inside_the_window():
    t = _trace()
    assert t.window_s == pytest.approx(0.040)
    # chip 0: [2, 9] + [12, 14] + [30, 42] = 21 ms; chip 1: [2, 10] = 8 ms
    assert t.busy_s == pytest.approx((0.021 + 0.008) / 2)


def test_kernel_and_collective_seconds():
    t = _trace()
    assert t.op_seconds(trace_reduce.is_mm_kernel) == pytest.approx(0.006 / 2)
    assert t.op_seconds(trace_reduce.is_collective) == pytest.approx(0.002 / 2)


def test_idle_gaps_are_named_by_the_covering_span():
    gaps = _trace().idle_gaps()
    names = [(n, round(s * 1e3)) for n, s in gaps]
    # chip 1 idles 10..42 ms, chip 0 14..30 and 9..12 ms
    assert names == [("bench.idle", 32), ("bench.idle", 16),
                     ("bench.window", 3)]
    b = _trace().breakdown()
    assert b["device_ops"][0][0] == "%fusion.2 fusion f32[8]{0}"
    assert len(b["idle_gaps"]) <= 10


RECORDED = BENCH / "testdata" / "train-rsmm-v5e-3steps.xplane.pb.gz"


def test_recorded_chip_trace():
    """3 steps of train-qwen3-0.6b-rsmm traced on one v5e (--seconds 2):
    the kernel's 7 launches per step, the step busy end to end."""
    from jax.profiler import ProfileData
    with gzip.open(RECORDED, "rb") as f:
        t = trace_reduce.from_profile(ProfileData.from_serialized_xspace(
            f.read()))
    assert t.chips == [0]
    assert t.window_s == pytest.approx(3.0544, abs=1e-3)
    assert 0.99 * t.window_s < t.busy_s <= t.window_s
    kernel = t.op_seconds(trace_reduce.is_mm_kernel)
    assert kernel / 3 == pytest.approx(0.8338, rel=1e-3)
    assert t.op_seconds(trace_reduce.is_collective) == 0
    launches = [n for n, _, _ in t.ops[0] if trace_reduce.is_mm_kernel(n)]
    assert len(launches) % 3 == 0
    b = t.breakdown()
    assert b["device_ops"][0][0].startswith("%_agg_nd_impl")
    assert {n for n, _ in b["idle_gaps"]} <= {"bench.window",
                                              "bench.dispatch", "bench.wait"}
