"""Readings the correctness limits are set from (not part of a run).

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
      --control-seeds 1,2,3 [--out FILE]

  python3 bench/calibrate.py --workload <serve cell> --sweep 10,20,40 \
      --seconds 15

Runs, in one process on the cell's chips (on one chip when only control
seeds are given), the program against the plain
reference on every seed (the lower readings), and on the control seeds
the control and the planted faults in the program's place (the upper
readings).  Prints one JSON line per reading and the largest program
reading and smallest control/fault reading of each compared number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sweep", default="",
                    help="offered rates: measure the knee instead")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    ns = ap.parse_args(argv)
    run.prepare_environment()
    cell = run.find_cell(ns.workload)
    # the references alone (no --seeds) run on one chip
    chips = cell.chips if ns.seeds else 1
    try:
        devs = run.check_chips(chips)[:chips]
    except run.NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 3
    run.enable_compile_cache()
    driver = run.load_module(run.BENCH / "drivers"
                             / f"{cell.traffic['driver']}.py")
    log = lambda r: print(json.dumps(r), flush=True)  # noqa: E731
    if ns.sweep:
        rows = driver.sweep(cell, devs, [float(r) for r in ns.sweep.split(",")],
                            ns.seconds, log)
        if ns.out:
            os.makedirs(os.path.dirname(ns.out) or ".", exist_ok=True)
            with open(ns.out, "w") as f:
                json.dump(rows, f, indent=1)
        return 0
    seeds = [int(s) for s in ns.seeds.split(",") if s]
    controls = [int(s) for s in ns.control_seeds.split(",") if s]
    rows = driver.calibrate(cell, devs, seeds, controls, log)
    summary = {}
    for r in rows:
        for k, v in r.items():
            if k in ("seed", "kind") or not isinstance(v, (int, float)):
                continue
            s = summary.setdefault(k, {"lower": None, "upper": {}})
            if r["kind"] == "program":
                s["lower"] = v if s["lower"] is None else max(s["lower"], v)
            else:
                u = s["upper"]
                u[r["kind"]] = v if r["kind"] not in u else min(u[r["kind"]], v)
    print(json.dumps({"summary": summary}), flush=True)
    if ns.out:
        os.makedirs(os.path.dirname(ns.out) or ".", exist_ok=True)
        with open(ns.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
