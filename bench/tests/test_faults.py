"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run of a real cell at a size the CPU holds (``tiny``: the same keys,
small sizes, float32 so that a sound run agrees with the reference to
rounding), with one fault planted in the program: a step or commit that
returns its state unchanged, half of the batch or cohort left out, and
an answer altered where it is produced.  (The one-chip cells have no
exchange between chips to leave out.)
"""

import json
import shutil

import numpy as np
import pytest

from bench import run


@pytest.fixture(scope="module")
def base(tiny, tmp_path_factory):
    out = tmp_path_factory.mktemp("f32")
    shutil.copytree(tiny, out, dirs_exist_ok=True)
    cfg = json.loads((out / "configs" / "qwen3-0.6b-dp.json").read_text())
    cfg["torch_dtype"] = "float32"
    (out / "configs" / "qwen3-0.6b-dp.json").write_text(json.dumps(cfg))
    return out


SERVE = {"name": "serve-fedbuff-resnet18-steady", "config": "fedbuff-resnet18",
         "traffic": "steady", "chips": 1, "why": "the serve driver on the CPU"}
SERVE_E2E = [{"name": n, "unit": u, "better": b, "bound": 0.25,
              "source": "host_clock", "workloads": [SERVE["name"]]}
             for n, u, b in (("update_p95_ms", "ms", "lower"),
                             ("updates_per_s", "updates/s", "higher"))]


def _run(base, bench, cell, seconds=1.0):
    if cell == SERVE["name"]:
        bench = dict(bench, workloads=bench["workloads"] + [SERVE],
                     end_to_end=bench["end_to_end"] + SERVE_E2E)
    return run.run_cell(cell, 2 ** 31 + 11, seconds, False, require_tpu=False,
                        bench=bench, base=base)


def _wrap_train_step(monkeypatch, wrap):
    from repro.launch import steps
    orig = steps.make_train_step_gspmd

    def make(*a, **kw):
        step, specs = orig(*a, **kw)
        return wrap(step), specs

    monkeypatch.setattr(steps, "make_train_step_gspmd", make)


def _unchanged(step):
    def f(params, opt, batch):
        _, _, met = step(params, opt, batch)
        return params, opt, met
    return f


def _half_batch(step):
    def f(params, opt, batch):
        t = batch["tokens"]
        k = 4
        t = t.reshape((k, t.shape[0] // k) + t.shape[1:])
        t = t[:, : t.shape[1] // 2].reshape((-1,) + batch["tokens"].shape[1:])
        return step(params, opt, {"tokens": t})
    return f


def _loss_altered(step):
    def f(params, opt, batch):
        p, o, met = step(params, opt, batch)
        return p, o, dict(met, loss=met["loss"] * 1.01)
    return f


TRAIN = ["train-qwen3-0.6b-rsmm", "train-qwen3-0.6b-mean"]


@pytest.mark.parametrize("cell", TRAIN)
def test_sound_train_run_is_correct(base, tiny_bench, cell):
    out = _run(base, tiny_bench, cell)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("cell", TRAIN)
@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _loss_altered],
                         ids=["state_unchanged", "half_batch", "loss_altered"])
def test_train_fault_is_not_correct(base, tiny_bench, monkeypatch, fault,
                                    cell):
    _wrap_train_step(monkeypatch, fault)
    out = _run(base, tiny_bench, cell)
    assert not out["correct"], out["checks"]


def _wrap_launch(monkeypatch, wrap):
    from repro.serve import service
    orig = service.AggregationService._launch

    def launch(self, x, a, degraded):
        return wrap(orig, self, x, a, degraded)

    monkeypatch.setattr(service.AggregationService, "_launch", launch)


def _commit_unchanged(orig, self, x, a, degraded):
    out = orig(self, x, a, degraded)
    return (self._w.copy(),) + tuple(out[1:])


def _half_cohort(orig, self, x, a, degraded):
    h = x.shape[0] // 2
    return orig(self, np.ascontiguousarray(x[:h]), a[:h], degraded)


def _estimate_altered(orig, self, x, a, degraded):
    out = orig(self, x, a, degraded)
    est = out[0].copy()
    est[0] += 0.1
    return (est,) + tuple(out[1:])


def test_sound_serve_run_is_correct(base, tiny_bench):
    out = _run(base, tiny_bench, "serve-fedbuff-resnet18-steady", 3.0)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [_commit_unchanged, _half_cohort,
                                   _estimate_altered],
                         ids=["state_unchanged", "half_cohort",
                              "estimate_altered"])
def test_serve_fault_is_not_correct(base, tiny_bench, monkeypatch, fault):
    _wrap_launch(monkeypatch, fault)
    out = _run(base, tiny_bench, "serve-fedbuff-resnet18-steady", 3.0)
    assert not out["correct"], out["checks"]
