"""``chip_smoke.py``: it refuses to run without a TPU, and each of its
phases passes at a tiny size on the CPU (kernels in interpret mode)."""

import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_a_host_without_tpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    out = _run(str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_kernel_phase_at_tiny_size(smoke, capsys):
    smoke.phase_kernel((
        ("single", 8, 1000, None, "single"),
        ("batched", 16, 300, 16, "single"),
        ("two-pass", 512, 256, None, "two_pass"),
    ))
    assert capsys.readouterr().out.count("max|kernel-ref|") == 3


def test_kernel_phase_fails_on_the_wrong_path(smoke):
    with pytest.raises(smoke.PhaseFailed, match="expected two_pass"):
        smoke.phase_kernel((("tiny", 8, 256, None, "two_pass"),))


def test_train_phase_at_tiny_size(smoke, capsys):
    smoke.phase_train(layers=1, steps=2, full_config=False, seq=16)
    out = capsys.readouterr().out
    assert "aggregation=rs_mm losses" in out
    assert "aggregation=mean losses" in out
    assert out.count("# step compile") == 2


def test_serve_phase_at_tiny_size(smoke, capsys):
    smoke.phase_serve(dim=256, rounds=3)
    assert "'launch_failed': 0" in capsys.readouterr().out
