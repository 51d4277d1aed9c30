"""End-to-end driver: train a transformer LM with Byzantine-robust
data-parallel gradient aggregation (the paper's technique lifted to the
training framework).

Default: a ~20M-param qwen3-family model, 300 steps, 8 simulated agents
(``--agents 8``, whatever the device count), one of which sends
additively-corrupted gradients.  Compares mean vs REF (rs_mm)
aggregation.

  PYTHONPATH=src python examples/train_robust_lm.py            # ~20M
  PYTHONPATH=src python examples/train_robust_lm.py --big      # ~100M
  (the full assigned configs run through the launcher: see
   src/repro/launch/train.py --full-config on a real cluster)
"""

import argparse
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "..", "src")


def run(agg, malicious, args):
    cmd = [
        sys.executable, "-m", "repro.launch.train",
        "--arch", "qwen3-0.6b",
        "--steps", str(args.steps),
        "--batch", "8",
        "--seq", str(args.seq),
        "--layers", str(args.layers),
        "--d-model", str(args.d_model),
        "--aggregation", agg,
        "--agents", "8",
        "--malicious", str(malicious),
        "--delta", "100.0",
        "--lr", "3e-3",
    ]
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    print(f"\n=== aggregation={agg} malicious={malicious} ===")
    proc = subprocess.run(cmd, env=env, text=True, capture_output=True)
    print(proc.stdout)
    if proc.returncode != 0:
        print(proc.stderr[-2000:])
        raise SystemExit(proc.returncode)
    last = [l for l in proc.stdout.splitlines() if l.startswith("# first-10")]
    return last[0] if last else ""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--big", action="store_true",
                    help="~100M params (slow on CPU)")
    args = ap.parse_args()
    if args.big:
        args.layers, args.d_model, args.seq = 8, 512, 256
    else:
        args.layers, args.d_model, args.seq = 4, 256, 128

    results = {}
    results["mean clean"] = run("mean", 0, args)
    results["mean attacked"] = run("mean", 1, args)
    results["REF attacked"] = run("rs_mm", 1, args)

    print("\n================ summary ================")
    for k, v in results.items():
        print(f"{k:16s} {v}")
    print("\nExpected: 'mean attacked' stalls near the initial loss;"
          "\n'REF attacked' tracks 'mean clean'.")


if __name__ == "__main__":
    main()
