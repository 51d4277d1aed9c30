"""Cells, configurations, traffic and per-layer metrics are found by
name, and new ones are picked up from new files alone."""

import json
import re
import shutil

from bench import run
from bench.tests.conftest import BENCH, ROOT, load

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
LINE = re.compile(r"[^\n\t]{1,200}")


def test_every_cell_resolves_to_its_files():
    bench = load(ROOT / "BENCHMARK.json")
    assert bench["command"] == ["python3", "bench/run.py"]
    for w in bench["workloads"]:
        cell = run.find_cell(w["name"], bench)
        assert cell.config["name"] == w["config"]
        assert (BENCH / "drivers" / f"{cell.traffic['driver']}.py").exists()
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert (BENCH / "metrics" / f"{m['name']}.py").exists()
            assert m["moves"] in names
    for c in bench["configs"]:
        assert (ROOT / c["file"]).exists()


def test_metric_without_workloads_key_follows_the_metric_it_moves():
    bench = load(ROOT / "BENCHMARK.json")
    bench = dict(bench, per_layer=bench["per_layer"] + [
        {"name": "x_ms.train", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "kernel", "moves": "step_ms"}])
    bench["workloads"].append({"name": "other", "config": "qwen3-0.6b-dp",
                               "traffic": "mean", "chips": 1, "why": "x"})
    bench["end_to_end"].append({"name": "other_ms", "unit": "ms",
                                "better": "lower", "bound": 0.05,
                                "source": "host_clock", "workloads": ["other"]})
    bench["end_to_end"][1] = dict(bench["end_to_end"][1], workloads=[
        w for w in bench["end_to_end"][1]["workloads"]])
    train = run.find_cell("train-qwen3-0.6b-rsmm", bench)
    other = run.find_cell("other", bench)
    assert "x_ms.train" in {m["name"] for m in train.per_layer}
    assert "x_ms.train" not in {m["name"] for m in other.per_layer}


def test_new_config_cell_and_metric_are_files_only(tmp_path):
    for d in ("configs", "traffic", "drivers", "metrics"):
        shutil.copytree(BENCH / d, tmp_path / d)
    cfg = load(BENCH / "configs" / "qwen3-0.6b-dp.json")
    cfg.update(name="qwen3-0.6b-dp-long")
    (tmp_path / "configs" / "qwen3-0.6b-dp-long.json").write_text(
        json.dumps(cfg))
    (tmp_path / "traffic" / "rsmm-x.json").write_text(json.dumps(
        dict(load(BENCH / "traffic" / "rsmm.json"), seq_len=1024)))
    (tmp_path / "metrics" / "answer.train.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench = load(ROOT / "BENCHMARK.json")
    bench["workloads"].append({"name": "train-long", "config":
                               "qwen3-0.6b-dp-long", "traffic": "rsmm-x",
                               "chips": 1, "why": "longer sequences"})
    bench["end_to_end"][1]["workloads"].append("train-long")
    bench["per_layer"].append({"name": "answer.train", "unit": "1",
                               "better": "higher", "source": "program_counter",
                               "layer": "kernel", "moves": "step_ms",
                               "workloads": ["train-long"]})
    cell = run.find_cell("train-long", bench, tmp_path)
    assert cell.config["name"] == "qwen3-0.6b-dp-long"
    assert cell.traffic["seq_len"] == 1024
    assert [m["name"] for m in cell.per_layer] == ["answer.train"]
    reader = run.load_module(tmp_path / "metrics" / "answer.train.py")
    assert reader.read(None) == 42.0


def test_benchmark_json_keeps_to_the_contract():
    bench = load(ROOT / "BENCHMARK.json")
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
        assert all(NAME.fullmatch(k) for k in c["reduced"])
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert NAME.fullmatch(w["config"]) and NAME.fullmatch(w["traffic"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower", "higher")
    everything = bench["configs"] + bench["workloads"] + bench["end_to_end"] \
        + bench["per_layer"]
    for e in everything:
        assert NAME.fullmatch(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert LINE.fullmatch(e[key]), (e["name"], key)
    for group in ("configs", "workloads"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metrics) == len(set(metrics))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
