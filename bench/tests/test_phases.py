"""The training step's phases read from a trace: the join of device
events to the compiled step's instructions, the outermost-only rule,
the set-up readers, and a trace recorded on a v5e chip
(``bench/testdata``)."""

import gzip

import pytest

from bench import phases, run, trace_reduce
from bench.tests.conftest import BENCH

MS = 1e6

STEP_HLO = """\
HloModule jit_step, is_scheduled=true

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc.3, metadata={op_name="jit(step)/agent_grads/vmap(transpose(jvp()))/while/body/mul" stack_frame_id=4}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%p, %fusion.3)
}

%fc.6 (q: f32[4,8]) -> f32[1,4,8] {
  %q = f32[4,8]{1,0} parameter(0)
  %select_n.1 = f32[4,8]{1,0} select(%q, %q, %q), metadata={op_name="jit(step)/attack/jit(_where)/select_n"}
  ROOT %bitcast.2 = f32[1,4,8]{2,1,0} bitcast(%select_n.1)
}

ENTRY %main (a: f32[8], b: f32[4,8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %b = f32[4,8]{1,0} parameter(1)
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.1, metadata={op_name="jit(step)/agent_grads/vmap(jvp())/dot_general" stack_frame_id=1}
  %while.2 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(step)/agent_grads/vmap(transpose(jvp()))/while" stack_frame_id=2}
  %fusion.6 = f32[1,4,8]{2,1,0} fusion(%b), kind=kLoop, calls=%fc.6
  %copy.8 = f32[1,4,8]{1,2,0} copy(%fusion.6), backend_config={"window_config":{}}
  %mm_aggregate.7 = f32[1,8]{1,0} custom-call(%copy.8, %w), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step)/aggregate/jit(_agg_nd_impl)/mm_aggregate/pallas_call"}, backend_config={"custom_call_config": {"body": "TUxJUgA="}}
  %fusion.1.remat = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.1, metadata={op_name="jit(step)/agent_grads/vmap(jvp())/dot_general" stack_frame_id=1}
  %fusion.9 = f32[8]{0} fusion(%mm_aggregate.7, %fusion.1.remat), kind=kLoop, calls=%fc.9, metadata={op_name="jit(step)/optimizer/add"}
  ROOT %copy.4 = f32[8]{0} copy(%fusion.9)
}
"""

# Device events as the profiler names them: the instruction's text with
# each operand's type and without the metadata.  The token feed's own
# program has a %fusion.1 too (another result type, no operand).
FEED_FUSION = ("%fusion.1 = u32[8]{0} fusion(), kind=kLoop, "
               "calls=%fused_computation.51")
EVENTS = [
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fc.1",
     0 * MS, 2 * MS),
    ("%while.2 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.0), "
     "condition=%cond, body=%body", 2 * MS, 10 * MS),
    ("%fusion.3 = f32[8]{0} fusion((s32[], f32[8]{0}) %p), kind=kLoop, "
     "calls=%fc.3", 3 * MS, 5 * MS),
    ("%fusion.3 = f32[8]{0} fusion((s32[], f32[8]{0}) %p), kind=kLoop, "
     "calls=%fc.3", 6 * MS, 9 * MS),
    ("%fusion.6 = f32[1,4,8]{2,1,0} fusion(f32[4,8]{1,0} %b), kind=kLoop, "
     "calls=%fc.6", 10 * MS, 11 * MS),
    ("%copy.8 = f32[1,4,8]{1,2,0} copy(f32[1,4,8]{2,1,0} %fusion.6)",
     11 * MS, 12 * MS),
    ("%mm_aggregate.7 = f32[1,8]{1,0} custom-call(f32[1,4,8]{1,2,0} %copy.8, "
     "f32[4,1]{1,0} %w), custom_call_target=\"tpu_custom_call\", "
     "frontend_attributes={kernel_metadata={}}", 12 * MS, 20 * MS),
    ("%fusion.1.remat = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, "
     "calls=%fc.1", 20 * MS, 21 * MS),
    ("%fusion.9 = f32[8]{0} fusion(f32[1,8]{1,0} %mm_aggregate.7, "
     "f32[8]{0} %fusion.1.remat), kind=kLoop, calls=%fc.9", 21 * MS, 23 * MS),
    ("%copy.4 = f32[8]{0} copy(f32[8]{0} %fusion.9)", 23 * MS, 24 * MS),
    (FEED_FUSION, 24 * MS, 26 * MS),
]


def _ctx(events=EVENTS, window=(1 * MS, 30 * MS), **facts):
    tr = trace_reduce.Trace(ops={0: list(events)},
                            spans=[("bench.window",) + window], window=window)
    return trace_reduce.ReadContext(
        trace=tr, facts=dict({"steps": 1, "step_hlo": STEP_HLO}, **facts))


def test_outermost_counts_a_body_once_through_its_while():
    evs = [("a", 0, 10), ("b", 2, 4), ("c", 4, 10), ("d", 10, 12),
           ("e", 11, 15), ("f", 15, 15)]
    # b and c lie inside a; e starts inside d and keeps what lies beyond
    assert phases.outermost(evs) == [("a", 0, 10), ("d", 10, 12),
                                     ("e", 12, 15)]
    assert phases.outermost([]) == []


@pytest.mark.parametrize("op_name, phase", [
    ("jit(step)/agent_grads/vmap(jvp())/while", "forward"),
    ("jit(step)/agent_grads/vmap(transpose(jvp()))/while", "backward"),
    ("jit(step)/agent_grads/vmap(transpose(jvp()))/while/body/closed_call/"
     "checkpoint/rematted_computation/dot_general", "backward"),
    ("jit(step)/attack/jit(_where)/select_n", "attack"),
    ("jit(step)/aggregate/jit(_agg_nd_impl)/mm_aggregate/pallas_call",
     "aggregate"),
    ("jit(step)/optimizer/sqrt", "optimizer"),
    ("jit(step)/pow", None),
    ("jit(step)/aggregated/add", None),
    ("", None),
    (None, None),
])
def test_phase_of_an_op_name(op_name, phase):
    assert phases.phase_of(op_name) == phase


def test_phases_keep_the_programs_scope_names():
    from repro.launch import steps
    assert phases.SCOPES == steps.STEP_PHASES


def test_the_join_is_by_the_whole_instruction():
    table = phases.parse_hlo(STEP_HLO)
    assert len(table) == 16          # parameters and tuples included
    for text, _, _ in EVENTS[:-1]:
        assert phases.key(text) in table, text
    # the feed's %fusion.1 shares the step's name, not its instruction
    assert phases.key(FEED_FUSION)[0] == "%fusion.1"
    assert phases.key(FEED_FUSION) not in table
    assert phases.key("not an instruction") is None


def test_instructions_without_metadata_take_a_phase():
    by_name = {k[0]: ph for k, ph in phases.parse_hlo(STEP_HLO).items()}
    assert by_name["%fusion.6"] == "attack"        # from its fused root
    assert by_name["%copy.8"] == "aggregate"       # from its user
    assert by_name["%fusion.1.remat"] == "backward"
    assert by_name["%fusion.1"] == "forward"
    assert by_name["%copy.4"] is None              # the root: no user


def test_phase_seconds_on_a_hand_made_trace():
    ctx = _ctx()
    sec = phases.phase_seconds(ctx.trace, STEP_HLO)
    # window 1..30 ms: forward 1..2; backward the while 2..10 (its body
    # counted through it) and the recomputation 20..21; attack 10..11;
    # aggregate the copy 11..12 and the kernel 12..20; optimizer 21..23;
    # the root copy and the feed's fusion in no phase
    want = {"forward": 1, "backward": 9, "attack": 1, "aggregate": 9,
            "optimizer": 2, None: 3}
    assert sec == pytest.approx({k: v * 1e-3 for k, v in want.items()})
    assert sum(sec.values()) == pytest.approx(ctx.trace.busy_s)
    assert phases.phase_ms(ctx, "aggregate") == pytest.approx(9.0)
    assert phases.phase_ms(_ctx(steps=3), "backward") == pytest.approx(3.0)


@pytest.mark.parametrize("phase", phases.PHASES)
def test_phase_ms_is_none_where_its_facts_are_missing(phase):
    bare = _ctx()
    bare.facts.clear()
    assert phases.phase_ms(bare, phase) is None
    assert phases.phase_ms(_ctx(steps=0), phase) is None
    # a parent program: the step's text without the scopes
    plain = STEP_HLO
    for scope in phases.SCOPES:
        plain = plain.replace(f"/{scope}/", "/")
    assert phases.phase_ms(_ctx(step_hlo=plain), phase) is None
    assert phases.phase_ms(_ctx(), phase) > 0


NEW_COUNTERS = {"setup_lower_s.train": ("trace_s", "lower_s"),
                "setup_compile_s.train": ("compile_s",)}


def _reader(name):
    return run.load_module(BENCH / "metrics" / f"{name}.py")


@pytest.mark.parametrize("name", NEW_COUNTERS)
def test_new_reader_is_none_where_its_facts_are_missing(name, monkeypatch):
    from repro import compat
    reader = _reader(name)
    zeros = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0, "compiles": 0}
    monkeypatch.setattr(compat, "compile_totals", lambda fun: dict(zeros))
    assert reader.read(_ctx()) is None          # the step never compiled
    monkeypatch.setattr(compat, "compile_totals",
                        lambda fun: dict(zeros, compiles=1, trace_s=1.0,
                                         lower_s=0.5, compile_s=2.0))
    assert reader.read(_ctx()) == (1.5 if name.startswith("setup_lower")
                                   else 2.0)
    monkeypatch.delattr(compat, "compile_totals")   # a parent program
    assert reader.read(_ctx()) is None


def test_setup_readers_see_the_train_steps_one_compile(tiny, tiny_bench):
    """A run of the train driver compiles the program's step, the
    function the readers name, once; the readers read its totals."""
    from repro import compat
    readers = {name: _reader(name) for name in NEW_COUNTERS}
    assert {r.STEP for r in readers.values()} == {"step"}
    before = compat.compile_totals("step")
    out = run.run_cell("train-qwen3-0.6b-rsmm", 2 ** 31 + 13, 0.5, False,
                       require_tpu=False, bench=tiny_bench, base=tiny)
    assert out["correct"]
    after = compat.compile_totals("step")
    assert after["compiles"] == before["compiles"] + 1
    for name, keys in NEW_COUNTERS.items():
        v = readers[name].read(_ctx())
        assert v == pytest.approx(sum(after[k] for k in keys))
        assert sum(after[k] - before[k] for k in keys) > 0


RECORDED = BENCH / "testdata" / "train-rsmm-v5e-3steps-phases"


def test_recorded_chip_trace_of_the_scoped_step():
    """3 steps of train-qwen3-0.6b-rsmm traced on one v5e (--seconds 2),
    with the compiled step's text: the five phases cover the busy device
    time, the aggregation holds the kernel, and the feed's program is
    left out."""
    from jax.profiler import ProfileData
    with gzip.open(f"{RECORDED}.xplane.pb.gz", "rb") as f:
        tr = trace_reduce.from_profile(ProfileData.from_serialized_xspace(
            f.read()))
    with gzip.open(f"{RECORDED}.hlo.txt.gz", "rt") as f:
        hlo = f.read()
    steps = 3
    sec = phases.phase_seconds(tr, hlo)
    assert sum(sec.get(p, 0.0) for p in phases.PHASES) >= 0.97 * tr.busy_s
    assert 0 < sec[None] < 1e-4 * tr.busy_s          # the token feed
    kernel = tr.op_seconds(trace_reduce.is_mm_kernel)
    assert kernel / steps == pytest.approx(0.92674, rel=1e-4)
    assert sec["aggregate"] >= kernel
    launches = {n.partition(" = ")[0].rsplit(".", 1)[0]
                for n, _, _ in tr.ops[0] if trace_reduce.is_mm_kernel(n)}
    assert launches == {"%mm_aggregate"}
    ctx = trace_reduce.ReadContext(trace=tr,
                                   facts={"steps": steps, "step_hlo": hlo})
    ms = {p: phases.phase_ms(ctx, p) for p in phases.PHASES}
    assert ms == pytest.approx({"forward": 73.83, "backward": 224.04,
                                "attack": 37.97, "aggregate": 957.19,
                                "optimizer": 71.43}, rel=1e-3)
