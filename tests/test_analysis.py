"""The static-analysis gate: findings/baseline machinery, the kernel
contract checker, the jaxpr auditor, the lint pass, and the BENCH-file
audits -- including the mutation fixtures that prove each pass catches
the defect class it exists for (a checker that never fires is
indistinguishable from a checker that works)."""

import json
import pathlib
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import bench_audit, contracts, jaxpr_audit, lint
from repro.analysis import findings as F
from repro.analysis.__main__ import main as analysis_main
from repro.kernels import mm_aggregate as mk

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


# ===========================================================================
# findings + baseline machinery
# ===========================================================================

def _finding(**kw):
    base = dict(rule="r", path="p", where="w", detail="d")
    base.update(kw)
    return F.Finding(**base)


def test_finding_key_excludes_line_numbers():
    assert _finding(line=5).key == _finding(line=900).key
    assert _finding(ident="a").key != _finding(ident="b").key


def test_load_baseline_missing_file_is_empty(tmp_path):
    assert F.load_baseline(tmp_path / "nope.json") == {}


def test_load_baseline_rejects_reasonless_entries(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"findings": [{"key": "r:p:w"}]}))
    with pytest.raises(F.BaselineError, match="reason"):
        F.load_baseline(p)
    p.write_text(json.dumps([{"key": "r:p:w", "reason": "   "}]))
    with pytest.raises(F.BaselineError, match="reason"):
        F.load_baseline(p)


def test_load_baseline_rejects_duplicates_and_bad_schema(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps([{"key": "k", "reason": "x"},
                             {"key": "k", "reason": "y"}]))
    with pytest.raises(F.BaselineError, match="duplicate"):
        F.load_baseline(p)
    p.write_text(json.dumps({"findings": "oops"}))
    with pytest.raises(F.BaselineError, match="list"):
        F.load_baseline(p)


def test_apply_splits_and_reports_stale():
    f1, f2 = _finding(ident="a"), _finding(ident="b")
    baseline = {f1.key: "intentional", "r:gone:w": "stale entry"}
    un, base, stale = F.apply([f1, f2], baseline)
    assert un == [f2]
    assert base == [(f1, "intentional")]
    assert stale == ["r:gone:w"]


# ===========================================================================
# contract checker: the real kernels are clean...
# ===========================================================================

def test_contracts_clean_on_default_workloads():
    assert contracts.check_workloads() == []


# ===========================================================================
# ...and each mutation fixture trips exactly the rule built for it
# ===========================================================================

def _plan_and_call(k=1024, m=1024, n=4, path="two_pass"):
    # pinned geometry so the fixture grid is honestly 2-D (4 M blocks x
    # 2 K blocks) -- a 1x1 grid can't distinguish index maps
    plan = mk.launch_plan(k, m, n, block_m=256, path=path)
    assert plan.grid[0] > 1 and plan.grid[1] > 1
    return plan, mk.kernel_call(plan, k=k)


def _rules(findings):
    return {(f.rule, f.ident) for f in findings}


def test_mutation_double_fetch_tile():
    # every K step re-fetches tile (0, mi): one-residency broken
    plan, call = _plan_and_call()
    bad_spec = pl.BlockSpec((plan.block_k, plan.block_m),
                            lambda mi, ki: (0, mi))
    bad = call._replace(in_specs=(bad_spec, call.in_specs[1]))
    got = _rules(contracts.audit_call(plan, bad))
    assert ("one-residency", "refetch") in got
    assert ("one-residency", "coverage") in got


def test_mutation_wrong_input_block_shape():
    plan, call = _plan_and_call()
    bad_spec = pl.BlockSpec((plan.block_k * 2, plan.block_m),
                            call.in_specs[0].index_map)
    bad = call._replace(in_specs=(bad_spec, call.in_specs[1]))
    assert ("one-residency", "block-shape") in _rules(
        contracts.audit_call(plan, bad))


def test_mutation_model_disagrees_with_fetch_count():
    # a plan whose modeled traffic is wrong must be caught, not trusted
    plan, call = _plan_and_call()
    lying = plan._replace(input_block_fetches=plan.input_block_fetches + 1)
    assert any(f.rule == "one-residency" and "fetches" in f.detail
               for f in contracts.audit_call(lying, call))


def test_mutation_per_step_weight_slices():
    plan, call = _plan_and_call()
    bad_spec = pl.BlockSpec((plan.k_pad, plan.n_out),
                            lambda mi, ki: (0, ki))
    bad = call._replace(in_specs=(call.in_specs[0], bad_spec))
    assert ("one-residency", "weights") in _rules(
        contracts.audit_call(plan, bad))


def test_mutation_output_tile_follows_k_axis():
    plan, call = _plan_and_call()
    bad_spec = pl.BlockSpec((plan.n_out, plan.block_m),
                            lambda mi, ki: (0, ki))
    bad = call._replace(out_specs=bad_spec)
    assert any(f.rule == "output-map"
               for f in contracts.audit_call(plan, bad))


def test_mutation_hbm_resident_stats():
    # the two-pass stats planes surface as a second HBM output
    plan, call = _plan_and_call()
    stats = jax.ShapeDtypeStruct(
        (plan.num_k_blocks, plan.n_out, plan.block_m), jnp.float32)
    bad = call._replace(out_shape=[call.out_shape, stats])
    got = _rules(contracts.audit_call(plan, bad))
    assert ("hbm-stats", "stats-output") in got
    assert ("hbm-stats", "") in got          # >1 HBM output at all


def test_mutation_inflated_scratch():
    plan, call = _plan_and_call()
    extra = pltpu.VMEM((plan.k_pad, plan.block_m), jnp.float32)
    bad = call._replace(scratch_shapes=call.scratch_shapes + (extra,))
    assert any(f.rule == "vmem-model"
               for f in contracts.audit_call(plan, bad))


def test_mutation_grid_mismatch_short_circuits():
    plan, call = _plan_and_call()
    bad = call._replace(grid=(call.grid[0] + 1, call.grid[1]))
    got = contracts.audit_call(plan, bad)
    assert [f.rule for f in got] == ["grid-mismatch"]


def test_vmem_budget_flags_avoidable_overflow_only():
    # K=16, N=64 at an absurd pinned tile: the double-buffered blocks
    # blow the budget but a narrower tile would fit -> avoidable -> flagged
    plan = mk.launch_plan(16, 1 << 20, 64, block_m=1 << 16, path="single")
    call = mk.kernel_call(plan, k=16)
    assert any(f.rule == "vmem-budget"
               for f in contracts.audit_call(plan, call))
    # forced small mesh: K=64 / N=4096 overflows even at a 128 tile (the
    # output blocks alone), and the two-pass crossover excludes it ->
    # sanctioned, not flagged
    plan = mk.launch_plan(64, 128, 4096, block_m=128, path="single")
    assert mk.single_pass_vmem_bytes(plan.k_pad, plan.n_out, 128) \
        > mk.VMEM_BUDGET_BYTES
    call = mk.kernel_call(plan, k=64)
    assert not any(f.rule == "vmem-budget"
                   for f in contracts.audit_call(plan, call))


@pytest.mark.parametrize("block_k", [None, 2])
def test_single_pass_rows_view_audits_clean_and_refetch_is_caught(block_k):
    """A leaf read as its own (64, 1024) matrix in (8, 1024) tiles, with
    K whole or streamed in two blocks (then through the residency),
    audits clean; an index map that fetches one tile for every grid
    step is caught."""
    plan = mk.launch_plan(4, 64 * 1024, 1, block_m=8192, block_k=block_k,
                          shape=(64, 1024))
    assert plan.view == "rows" and plan.grid[0] == 8
    assert (plan.block_rows, plan.block_cols) == (8, 1024)
    call = mk.kernel_call(plan, k=4)
    assert bool(call.scratch_shapes) == (block_k is not None)
    assert contracts.audit_call(plan, call) == []
    bad_spec = pl.BlockSpec(call.in_specs[0].block_shape,
                            lambda t, ki: (ki, 0, 0))
    bad = call._replace(in_specs=(bad_spec, call.in_specs[1]))
    got = _rules(contracts.audit_call(plan, bad))
    assert ("one-residency", "refetch") in got
    assert ("one-residency", "coverage") in got


def test_heuristic_blocks_respect_the_vmem_model():
    # the finding the analyzer's first run surfaced: the heuristic must
    # consult the kernel's own model, not an optimistic private one
    from repro.kernels import tuning
    bm, _ = tuning.heuristic_blocks(33, 700, 5)
    assert mk.single_pass_vmem_bytes(34, 5, bm) <= mk.VMEM_BUDGET_BYTES
    # ...while large-K cohorts keep the wide tile the two-pass path
    # affords instead of starving single-pass under the budget
    bm, _ = tuning.heuristic_blocks(512, 256, 1)
    assert bm == 256
    assert mk.auto_path(512, 1, bm) == "two_pass"


# ===========================================================================
# jaxpr auditor: clean on the real programs...
# ===========================================================================

def test_jaxpr_audit_engine_and_donation_clean():
    assert jaxpr_audit.check_engine() == []
    assert jaxpr_audit.check_donation() == []


def test_jaxpr_audit_scenarios_clean():
    assert jaxpr_audit.check_scenarios() == []


# ===========================================================================
# ...and the mutation fixtures trip it
# ===========================================================================

def test_mutation_callback_in_steady_path():
    def step(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    jx = jax.make_jaxpr(step)(jnp.ones(4))
    got = jaxpr_audit.audit_program(jx, where="fixture")
    assert any(f.rule == "callback" for f in got)


def test_mutation_callback_inside_scan_is_found():
    # the recursion into sub-jaxprs is what makes the rule real
    def body(c, _):
        c = jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct(c.shape, c.dtype),
            c)
        return c, None

    def prog(x):
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    jx = jax.make_jaxpr(prog)(jnp.ones(4))
    assert any(f.rule == "callback"
               for f in jaxpr_audit.audit_program(jx, where="scan-fixture"))


def test_mutation_pallas_count():
    jx = jax.make_jaxpr(lambda x: x * 2)(jnp.ones(4))
    got = jaxpr_audit.audit_program(jx, where="fixture", expect_pallas=1)
    assert any(f.rule == "pallas-count" for f in got)


def test_mutation_bf16_stream_upcast():
    from repro.kernels import ops
    eng = ops.AggregationEngine(interpret=True)

    def leaky(x):                  # upcasts the stream before the kernel
        return eng.aggregate(x.astype(jnp.float32))

    jx = jax.make_jaxpr(leaky)(jnp.zeros((8, 300), jnp.bfloat16))
    got = jaxpr_audit.audit_program(jx, where="fixture",
                                    stream_dtype=jnp.bfloat16)
    assert any(f.rule == "bf16-stream" and f.ident == "input" for f in got)


# ===========================================================================
# lint pass: the tree is clean, the fixtures are not
# ===========================================================================

def test_lint_tree_is_clean():
    assert lint.check_tree(REPO_ROOT) == []


def _lint(src):
    return lint.lint_source(textwrap.dedent(src))


def test_lint_traced_branch_in_jit():
    got = _lint("""
        import jax
        @jax.jit
        def step(x, lr):
            if x > 0:
                return x * lr
            return x
    """)
    assert any(f.rule == "traced-branch" for f in got)


def test_lint_static_argnames_are_exempt():
    got = _lint("""
        import functools
        import jax
        @functools.partial(jax.jit, static_argnames=("mode",))
        def step(x, mode):
            if mode == "fast":
                return x
            return x * 2
    """)
    assert not any(f.rule == "traced-branch" for f in got)


def test_lint_assignment_form_jit_with_constant_statics():
    src = """
        import jax
        _STATICS = ("mode",)
        def _impl(x, mode):
            if {cond}:
                return x
            return -x
        impl = jax.jit(_impl, static_argnames=_STATICS)
    """
    assert not any(f.rule == "traced-branch"
                   for f in _lint(src.format(cond="mode")))
    assert any(f.rule == "traced-branch"
               for f in _lint(src.format(cond="x > 0")))


def test_lint_shape_metadata_is_static():
    got = _lint("""
        import jax
        @jax.jit
        def step(x):
            if x.shape[0] > 4:
                return x[:4]
            return x
    """)
    assert not any(f.rule == "traced-branch" for f in got)


def test_lint_traced_branch_in_kernel_body():
    got = _lint("""
        def agg_kernel(x_ref, o_ref):
            while x_ref[0] > 0:
                o_ref[0] = x_ref[0]
    """)
    assert any(f.rule == "traced-branch" and "while" in f.ident
               for f in got)


def test_lint_host_sync():
    got = _lint("""
        import jax
        import numpy as np
        @jax.jit
        def step(x):
            a = x.item()
            b = float(x)
            c = np.asarray(x)
            return a + b + c
    """)
    idents = {f.ident for f in got if f.rule == "host-sync"}
    assert {"item", "float", "np.asarray"} <= idents


def test_lint_host_sync_only_in_traced_scope():
    got = _lint("""
        def plain(x):
            return float(x)
    """)
    assert not any(f.rule == "host-sync" for f in got)


def test_lint_mutable_default():
    got = _lint("""
        def collect(row, acc=[]):
            acc.append(row)
            return acc
    """)
    assert any(f.rule == "mutable-default" and f.ident == "acc"
               for f in got)


def test_lint_spec_dataclass_rules():
    got = _lint("""
        import dataclasses
        @dataclasses.dataclass
        class RunSpec:
            steps: int = 5
    """)
    assert any(f.rule == "spec-dataclass" and f.ident == "not-frozen"
               for f in got)
    got = _lint("""
        import dataclasses
        @dataclasses.dataclass(frozen=True)
        class RunSpec:
            steps: int = 5
            hooks: list = dataclasses.field(default_factory=list)
    """)
    assert any(f.rule == "spec-dataclass" and f.ident == "field-hooks"
               for f in got)
    # non-spec-suffixed classes are out of scope for the frozen rule
    got = _lint("""
        import dataclasses
        @dataclasses.dataclass
        class RunResult:
            loss: float = 0.0
    """)
    assert not any(f.rule == "spec-dataclass" for f in got)


def test_lint_import_time_jnp():
    got = _lint("""
        import jax.numpy as jnp
        ZEROS = jnp.zeros((4,))
    """)
    assert any(f.rule == "import-time-jnp" for f in got)
    got = _lint("""
        import jax.numpy as jnp
        DT = jnp.dtype("float32")
        def fn():
            return jnp.zeros((4,))
    """)
    assert not any(f.rule == "import-time-jnp" for f in got)


# ===========================================================================
# the CLI gate end to end (tmp repo -> fail -> baseline -> pass -> stale)
# ===========================================================================

def test_cli_gate_baseline_workflow(tmp_path, capsys):
    src = tmp_path / "src"
    src.mkdir()
    bad = src / "bad.py"
    bad.write_text("def f(xs=[]):\n    return xs\n")

    assert analysis_main(["--passes", "lint", "--root", str(tmp_path)]) == 1
    assert "mutable-default" in capsys.readouterr().out

    key = lint.lint_file(bad, tmp_path)[0].key
    (tmp_path / "ANALYSIS_BASELINE.json").write_text(json.dumps(
        {"findings": [{"key": key, "reason": "fixture: kept on purpose"}]}))
    assert analysis_main(["--passes", "lint", "--root", str(tmp_path)]) == 0
    assert "kept on purpose" in capsys.readouterr().out

    # fixing the file leaves a stale baseline entry: reported, not fatal
    bad.write_text("def f(xs=()):\n    return xs\n")
    out_json = tmp_path / "report.json"
    assert analysis_main(["--passes", "lint", "--root", str(tmp_path),
                          "--json", str(out_json)]) == 0
    assert "stale" in capsys.readouterr().out
    report = json.loads(out_json.read_text())
    assert report["stale_baseline_keys"] == [key]


def test_cli_rejects_unknown_pass(tmp_path):
    (tmp_path / "src").mkdir()
    with pytest.raises(ValueError, match="unknown pass"):
        analysis_main(["--passes", "nope", "--root", str(tmp_path)])


# ===========================================================================
# BENCH-file audits (the rules that used to live as ci.sh heredocs)
# ===========================================================================

def _good_agg():
    return {
        "traffic_audit": [
            {"name": "s", "path": "single", "n_independent": True},
            {"name": "t", "path": "two_pass", "n_independent": True},
        ],
        "rows": [{"name": "agg/mm_pallas_two_pass/K256xM4096"}],
        "irls_sweep": [{"iters": 10}],
    }


def test_bench_audit_agg_good():
    assert bench_audit.audit_agg(_good_agg()) == []


def test_bench_audit_agg_violations():
    b = _good_agg()
    b["traffic_audit"] = b["traffic_audit"][:1]        # single only
    b["traffic_audit"][0]["n_independent"] = False
    b["rows"] = []
    b["irls_sweep"] = []
    errors = bench_audit.audit_agg(b)
    assert len(errors) == 4
    joined = "\n".join(errors)
    assert "paths incomplete" in joined
    assert "N-dependent" in joined
    assert "K=256" in joined
    assert "IRLS" in joined


def _cohort_row(k_pad=512, n_out=1, block_m=256, path="two_pass",
                vmem_bytes=None):
    if vmem_bytes is None:
        vmem_bytes = mk.two_pass_vmem_bytes(
            k_pad, n_out, block_m, mk.two_pass_block_k(k_pad),
            mk.two_pass_n_chunk(n_out, block_m, mk.two_pass_block_k(k_pad)))
    return {"name": f"K{k_pad}", "launch_audit": {
        "path": path, "k_pad": k_pad, "n_out": n_out,
        "block_m": block_m, "vmem_bytes": vmem_bytes}}


def test_bench_audit_large_cohort_good():
    assert bench_audit.audit_large_cohort({"rows": [_cohort_row()]}) == []


def test_bench_audit_large_cohort_violations():
    assert bench_audit.audit_large_cohort({"rows": []}) \
        == ["no two-pass scenario in the large-cohort family"]
    over = _cohort_row(vmem_bytes=mk.VMEM_BUDGET_BYTES + 1)
    assert any("exceeds the VMEM budget" in e
               for e in bench_audit.audit_large_cohort({"rows": [over]}))
    # two-pass engaged on a shape whose single-pass model fits
    small = _cohort_row(k_pad=8, n_out=1, block_m=128, vmem_bytes=1024)
    assert any("single-pass model fits" in e
               for e in bench_audit.audit_large_cohort({"rows": [small]}))


def test_bench_audit_kind_inference_and_cli(tmp_path, capsys):
    assert bench_audit.infer_kind("BENCH_agg.json") == "agg"
    assert bench_audit.infer_kind("BENCH_large_cohort.json") == "large_cohort"
    with pytest.raises(ValueError, match="cannot infer"):
        bench_audit.infer_kind("BENCH_other.json")

    good = tmp_path / "BENCH_agg.json"
    good.write_text(json.dumps(_good_agg()))
    assert bench_audit.main([str(good)]) == 0
    assert "audit ok" in capsys.readouterr().out

    bad = tmp_path / "BENCH_large_cohort.json"
    bad.write_text(json.dumps({"rows": []}))
    assert bench_audit.main([str(bad)]) == 1
    assert "no two-pass scenario" in capsys.readouterr().out
