"""Run one benchmark cell once on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the checkout's
root.  Its configuration lives in ``bench/configs/<config>.json`` and its
traffic in ``bench/traffic/<traffic>.json``; the traffic names the
driver (``bench/drivers/<driver>.py``) that builds the system under test
from ``src/``, warms up, measures for ``--seconds`` seconds and checks
what the timed path produced against the plain reference under
``bench/reference/``.  With ``--trace 1`` the run is traced by the JAX
profiler and the cell's per-layer metrics are read by the readers in
``bench/metrics/<metric>.py``.

Informational lines go to stdout first; the compared numbers with their
limits are the last lines on stderr; the last stdout line is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(+ ``breakdown`` when traced) and, last, ``checks``.

The run refuses (exit 3, no result) a machine whose JAX finds no TPU or
fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]      # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a benchmark file by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def find_cell(name: str, bench: Optional[dict] = None,
              base: pathlib.Path = BENCH) -> Cell:
    """The cell ``name`` with its configuration, traffic and the metrics
    it reports, all found by name under ``base`` (``bench/``): adding a
    cell, a configuration, a traffic mix or a metric adds files there and
    entries in BENCHMARK.json, and edits nothing."""
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(base / "configs" / f"{w['config']}.json"),
        traffic=load_json(base / "traffic" / f"{w['traffic']}.json"),
        end_to_end=e2e, per_layer=per_layer)


def prepare_environment() -> None:
    """Process settings every cell runs under: the blocks the program
    picks by itself (no tuning file), the compile cache at a fixed path
    inside the checkout, libtpu's logs off the shared /tmp, and the
    program's sources importable."""
    os.environ.pop("REPRO_TUNING_CACHE", None)
    cache = ROOT / ".jax_compile_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def enable_compile_cache() -> None:
    """The program's persistent compile cache, at the fixed directory
    inside the checkout, without size-based eviction: eviction keeps an
    access-time file per entry, and one entry without it makes every
    later write fail."""
    import jax
    from repro import compat
    compat.enable_persistent_compilation_cache()
    jax.config.update("jax_compilation_cache_max_size", -1)


def check_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX sees {len(devs)} "
                     f"{devs[0].platform} device(s); the benchmark never "
                     "falls back to the CPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX sees "
                     f"{len(devs)}")
    return devs


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [int(p) for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class RunContext:
    """What a driver gets: the cell, the run's arguments and a few hooks
    into the harness."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_process: float
    log: Callable[[str], None]
    trace_dir: pathlib.Path = TRACE_DIR

    def start_trace(self) -> None:
        if self.trace:
            import jax
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # the benchmark's spans only
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)

    def stop_trace(self) -> None:
        if self.trace:
            import jax
            jax.profiler.stop_trace()


@dataclasses.dataclass
class DriverResult:
    """What a driver hands back.  ``metrics`` are end-to-end values by
    name; ``checks`` maps each compared number to (value, limit);
    ``facts`` is what the per-layer readers may read besides the trace
    (counters, spans timed by the harness, work counts)."""

    metrics: Dict[str, float]
    checks: Dict[str, tuple]
    attempted: int
    failed: int
    memory_peak_bytes: Optional[int]
    facts: Dict[str, Any] = dataclasses.field(default_factory=dict)
    correct: Optional[bool] = None     # None: every check within its limit

    def is_correct(self) -> bool:
        within = all(v is not None and v <= lim
                     for v, lim in self.checks.values())
        return within if self.correct is None else (self.correct and within)


def result_line(cell: Cell, res: DriverResult, dev: dict, trace: bool,
                layer: Optional[dict] = None) -> dict:
    """The JSON object of the run's last stdout line."""
    if trace:
        wanted = cell.per_layer
        values = (layer or {}).get("metrics", {})
    else:
        wanted = cell.end_to_end
        values = res.metrics
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if values.get(m["name"]) is not None}
    device = dict(dev, memory_peak_bytes=res.memory_peak_bytes)
    out = {"correct": res.is_correct(), "attempted": int(res.attempted),
           "failed": int(res.failed), "metrics": metrics, "device": device}
    if trace and layer is not None:
        device["busy_s"] = layer["busy_s"]
        device["window_s"] = layer["window_s"]
        out["breakdown"] = layer["breakdown"]
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in res.checks.items()}
    return out


def read_layers(cell: Cell, res: DriverResult, n_chips: int,
                base: pathlib.Path = BENCH) -> dict:
    """Reduce the run's trace and call each per-layer reader the cell
    reports.  A reader that finds nothing returns None and its metric is
    left out."""
    from bench import trace_reduce
    tr = trace_reduce.load(TRACE_DIR, n_chips=n_chips)
    ctx = trace_reduce.ReadContext(trace=tr, facts=res.facts)
    metrics = {}
    for m in cell.per_layer:
        reader = load_module(base / "metrics" / f"{m['name']}.py")
        v = reader.read(ctx)
        if v is not None:
            metrics[m["name"]] = v
    return {"metrics": metrics, "busy_s": tr.busy_s, "window_s": tr.window_s,
            "breakdown": tr.breakdown()}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, bench: Optional[dict] = None,
             base: pathlib.Path = BENCH, t_process: float = T_PROCESS) -> dict:
    """One run of one cell; returns the result object.  ``require_tpu``
    is the harness's look for a chip (the CPU tests skip it)."""
    prepare_environment()
    cell = find_cell(name, bench, base)
    import jax
    if require_tpu:
        devs = check_chips(cell.chips)
        enable_compile_cache()
    else:
        devs = jax.devices()
    devs = devs[:cell.chips]
    dev = device_info(devs)
    print(f"# cell {name} seed {seed} seconds {seconds} trace {int(trace)} "
          f"on {dev['count']} x {dev['kind']} ({dev['platform']})",
          flush=True)
    driver = load_module(base / "drivers" / f"{cell.traffic['driver']}.py")
    ctx = RunContext(cell=cell, seed=seed, seconds=seconds, trace=trace,
                     devices=devs, t_process=t_process,
                     log=lambda s: print(s, flush=True))
    res = driver.run(ctx)
    layer = read_layers(cell, res, len(devs), base) if trace else None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    gc.collect()
    return result_line(cell, res, dev, trace, layer)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ns = ap.parse_args(argv)
    try:
        out = run_cell(ns.workload, ns.seed, ns.seconds, bool(ns.trace))
    except NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 3
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
