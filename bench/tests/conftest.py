"""CPU fixtures: the benchmark's own cells at a size a test run holds.

``tiny`` is a directory laid out like ``bench/`` (configs, traffic, and
the real drivers and metric readers) whose configurations keep every
key of the real files and shrink only the sizes; ``tiny_bench`` is the
matching BENCHMARK.json object.
"""

import json
import os
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# The serve cell waits for a kernel that compiles at K=10 (PERF.md,
# section 7), so it has no traffic file under bench/traffic yet; this is
# the mix its driver is tested with here, at a small dimension.
SERVE_TRAFFIC = {
    "driver": "serve", "rate_per_s": 40.0, "clients": 1000, "zipf_s": 1.1,
    "attacker_ranks": [3, 6, 9, 12], "attacker_share": 0.1,
    "pool_honest": 32, "pool_attack": 4, "optimum_scale": 0.05,
    "honest_sd": 0.01, "attack_shift_sd": 100.0, "staleness_max": 2,
    "drain_s": 5.0, "calibrate_seconds": 10,
    "limits": {"model_gap_sd": 0.5, "commits_mismatched": 0}}

TINY_TRAIN = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
                  vocab_size=500)


def load(path):
    with open(path) as f:
        return json.load(f)


def write_traffic(base, **train):
    """The benchmark's traffic files under ``base``, the training mixes
    with ``train`` (a smaller ``seq_len``) over their own keys."""
    for t in (BENCH / "traffic").glob("*.json"):
        mix = load(t)
        if mix["driver"] == "train":
            mix.update(train)
        (base / "traffic" / t.name).write_text(json.dumps(mix))


@pytest.fixture(scope="session")
def tiny(tmp_path_factory):
    base = tmp_path_factory.mktemp("bench")
    for d in ("configs", "traffic"):
        (base / d).mkdir()
    for d in ("drivers", "metrics"):
        shutil.copytree(BENCH / d, base / d)
    cfg = load(BENCH / "configs" / "qwen3-0.6b-dp.json")
    cfg.update(TINY_TRAIN)
    (base / "configs" / "qwen3-0.6b-dp.json").write_text(json.dumps(cfg))
    fb = load(BENCH / "configs" / "fedbuff-resnet18.json")
    fb["dim"] = 4096
    (base / "configs" / "fedbuff-resnet18.json").write_text(json.dumps(fb))
    write_traffic(base, seq_len=16)
    (base / "traffic" / "steady.json").write_text(json.dumps(SERVE_TRAFFIC))
    return base


@pytest.fixture(scope="session")
def tiny_bench():
    return load(ROOT / "BENCHMARK.json")
