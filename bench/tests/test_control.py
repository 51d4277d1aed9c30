"""The control comes out as not correct.

The control is the plain reference put in the program's place and
computed a precision step below what the configuration states: matmul
operands in int8 (one absmax scale per tensor) where the program runs
bfloat16.  On the chip at the cell's size it was read on three seeds
(PERF.md); here, at a size a test run holds and with the cells' own
limits, the program (bfloat16, as configured) stays within every limit
and the control fails at least one.
"""

import json

import pytest

from bench import run
from bench.drivers import train
from bench.reference import qwen3
from bench.tests.conftest import BENCH, load, write_traffic

SMALL = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
             vocab_size=1000)
SEEDS = (1, 2)


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    base = tmp_path_factory.mktemp("small")
    for d in ("configs", "traffic"):
        (base / d).mkdir()
    cfg = load(BENCH / "configs" / "qwen3-0.6b-dp.json")
    cfg.update(SMALL)
    assert cfg["torch_dtype"] == "bfloat16"
    (base / "configs" / "qwen3-0.6b-dp.json").write_text(json.dumps(cfg))
    write_traffic(base, seq_len=32)
    for d in ("drivers", "metrics"):
        (base / d).symlink_to(BENCH / d)
    return base


@pytest.mark.parametrize("cell", ["train-qwen3-0.6b-rsmm",
                                  "train-qwen3-0.6b-mean"])
@pytest.mark.parametrize("seed", SEEDS)
def test_program_within_and_control_beyond_the_limits(small, tiny_bench,
                                                      cell, seed):
    out = run.run_cell(cell, seed, 1.0, False, require_tpu=False,
                       bench=tiny_bench, base=small)
    assert out["correct"], out["checks"]
    c = run.find_cell(cell, tiny_bench, small)
    job = train.job_of(c)
    ref = qwen3.run(c.config, job, seed, int(job["check_steps"]))
    ctl = qwen3.run(c.config, job, seed, int(job["check_steps"]),
                    matmul="int8")
    gaps = train.gaps(ctl, ref)
    over = {k: v for k, v in gaps.items() if v > c.traffic["limits"][k]}
    assert over, gaps
