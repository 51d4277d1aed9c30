# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point.

  fig1_msd     -- the paper's only figure (MSD sweeps, claim checks)
  agg_bench    -- aggregator cost table (systems counterpart)
  kernel_bench -- Pallas MM kernel vs jnp oracle
  roofline     -- per (arch x shape) roofline terms from the dry-run

Run: PYTHONPATH=src python -m benchmarks.run [--only fig1,agg,kernel,roofline]
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="fig1,agg,kernel,roofline")
    ap.add_argument("--fig1-iters", type=int, default=None)
    args = ap.parse_args()
    wanted = set(args.only.split(","))
    from repro import compat
    compat.enable_persistent_compilation_cache()

    suites = []
    if "fig1" in wanted:
        from benchmarks import fig1_msd
        suites.append(("fig1", lambda: fig1_msd.main(iters=args.fig1_iters)))
    if "agg" in wanted:
        from benchmarks import agg_bench
        # agg_bench.main returns (rows, audits); rows carry extra
        # bytes/launch columns for BENCH_agg.json
        suites.append(("agg",
                       lambda: [r[:3] for r in agg_bench.main()[0]]))
    if "kernel" in wanted:
        from benchmarks import kernel_bench
        suites.append(("kernel", kernel_bench.main))
    if "roofline" in wanted:
        from benchmarks import roofline
        suites.append(("roofline", roofline.main))

    print("name,us_per_call,derived")
    failed = False
    for name, fn in suites:
        try:
            for row in fn():
                n, us, derived = row
                print(f"{n},{us:.2f},{derived}")
        except Exception:  # noqa: BLE001
            failed = True
            print(f"{name},ERROR,see-stderr")
            traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
