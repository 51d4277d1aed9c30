"""The robust step on a data=4 mesh, one agent per chip, with the
exchange between chips left out comes out not correct.  The cell is not
in BENCHMARK.json yet (PERF.md, Open questions); the driver's mesh path
is kept and tested here.  Four virtual CPU devices stand in for the 2x2
host; the run goes through the harness with the look for a chip
skipped.  Each case runs in a child process, since the device count is
fixed when JAX starts."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench.tests.conftest import ROOT

SCRIPT = textwrap.dedent("""
    import json, pathlib, sys
    sys.path[:0] = [{root!r}, {src!r}]
    from bench import run
    fault = sys.argv[1]
    if fault == "no_exchange":
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.launch import steps

        def local_only(grads, mesh, par, out_specs, agg_axes):
            # each chip keeps its own agent's gradient: no all-to-all,
            # no gather, the replicas' parameters drift apart
            import jax
            a = agg_axes if len(agg_axes) > 1 else agg_axes[0]
            return jax.tree.map(
                lambda g: compat.shard_map(
                    lambda t: t[0], mesh=mesh, in_specs=P(a),
                    out_specs=P())(g), grads)

        steps.aggregate_stack = local_only
    bench = json.loads(pathlib.Path({bench!r}).read_text())
    out = run.run_cell("train-qwen3-0.6b-rsmm-dp4", 2 ** 31 + 9, 1.0, False,
                       require_tpu=False, bench=bench,
                       base=pathlib.Path({base!r}))
    print("RESULT " + json.dumps(out))
""")


def _bench(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if not any(w["name"] == "train-qwen3-0.6b-rsmm-dp4"
               for w in bench["workloads"]):
        bench["workloads"].append({"name": "train-qwen3-0.6b-rsmm-dp4",
                                   "config": "qwen3-0.6b-dp",
                                   "traffic": "rsmm", "chips": 4,
                                   "why": "x"})
        bench["end_to_end"][1]["workloads"].append("train-qwen3-0.6b-rsmm-dp4")
    p = tmp_path / "bench.json"
    p.write_text(json.dumps(bench))
    return p


def _run(tiny, tmp_path, fault):
    script = SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"),
                           bench=str(_bench(tmp_path)), base=str(tiny))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script, fault], env=env,
                       capture_output=True, text=True, timeout=600)
    lines = [l for l in p.stdout.splitlines() if l.startswith("RESULT ")]
    assert lines, p.stderr[-3000:]
    return json.loads(lines[-1][len("RESULT "):])


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("no_exchange", False)])
def test_dp4_exchange_left_out(tiny, tmp_path, fault, correct):
    out = _run(tiny, tmp_path, fault)
    assert out["device"]["count"] == 4
    assert out["correct"] is correct, out["checks"]
