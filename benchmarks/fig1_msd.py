"""Paper Fig. 1 reproduction: MSD over iterations for
(left) a single malicious agent across contamination strengths delta, and
(right) fixed delta=1000 across contamination rates.

Writes experiments/fig1_left.csv / fig1_right.csv (one MSD column per
(aggregator, delta-or-rate)) plus a summary of steady-state MSDs, and
checks the paper's three claims in band form.
"""

from __future__ import annotations

import os
import time

import jax
import numpy as np

from repro import compat
from repro.configs import paper_lsq
from repro.core import attacks, diffusion, graph
from repro.data import synthetic

AGGS = ("mean", "median", "mm_tukey")


def _run(agg: str, n_mal: int, delta: float, iters: int, seed: int = 0):
    prob = synthetic.LinearModelProblem(dim=paper_lsq.DIM,
                                        noise_var=paper_lsq.NOISE_VAR)
    comb = graph.uniform_weights(graph.fully_connected(paper_lsq.NUM_AGENTS))
    byz = attacks.ByzantineConfig(
        num_malicious=n_mal, attack="additive",
        attack_kwargs=(("delta", delta),))
    cfg = diffusion.DiffusionConfig(step_size=paper_lsq.STEP_SIZE,
                                    aggregator=agg, byzantine=byz)
    _, hist = diffusion.run_diffusion(
        grad_fn=prob.grad_fn(), combination=comb, config=cfg,
        w_star=prob.w_star, num_iters=iters, key=jax.random.key(seed))
    return np.asarray(hist)


def steady(h: np.ndarray, frac: float = 0.2) -> float:
    return float(np.mean(h[-max(1, int(len(h) * frac)):]))


def main(iters: int = None, out_dir: str = "experiments") -> list[tuple]:
    iters = iters or paper_lsq.NUM_ITERS
    os.makedirs(out_dir, exist_ok=True)
    rows = []

    # ---- left panel: single attacker, delta sweep -----------------------
    left_cols, left_names = [], []
    for agg in AGGS:
        for delta in paper_lsq.DELTA_GRID:
            t0 = time.perf_counter()
            h = _run(agg, 1 if delta > 0 else 0, delta, iters)
            dt = (time.perf_counter() - t0) * 1e6 / iters
            left_cols.append(h)
            left_names.append(f"{agg}_d{delta:g}")
            rows.append((f"fig1_left/{agg}/delta={delta:g}", dt, steady(h)))
    np.savetxt(os.path.join(out_dir, "fig1_left.csv"),
               np.stack(left_cols, 1), delimiter=",",
               header=",".join(left_names), comments="")

    # ---- right panel: fixed delta=1000, rate sweep -----------------------
    right_cols, right_names = [], []
    for agg in AGGS:
        for n_mal in paper_lsq.RATE_GRID:
            t0 = time.perf_counter()
            h = _run(agg, n_mal, 1000.0, iters)
            dt = (time.perf_counter() - t0) * 1e6 / iters
            right_cols.append(h)
            right_names.append(f"{agg}_m{n_mal}")
            rows.append((f"fig1_right/{agg}/mal={n_mal}", dt, steady(h)))
    np.savetxt(os.path.join(out_dir, "fig1_right.csv"),
               np.stack(right_cols, 1), delimiter=",",
               header=",".join(right_names), comments="")

    # ---- beyond-paper ablation: raised Tukey c for small-K efficiency ----
    # The median/MAD init supplies the breakdown point, so the refinement
    # loss can be widened (c=8 ~ 99% asymptotic efficiency) without losing
    # robustness at K=32 -- see EXPERIMENTS.md "Beyond-paper".
    from repro.core import diffusion as _d  # noqa -- reuse helpers
    for n_mal, delta in ((0, 0.0), (1, 1000.0), (11, 1000.0)):
        prob = synthetic.LinearModelProblem(dim=paper_lsq.DIM,
                                            noise_var=paper_lsq.NOISE_VAR)
        comb = graph.uniform_weights(
            graph.fully_connected(paper_lsq.NUM_AGENTS))
        byz = attacks.ByzantineConfig(
            num_malicious=n_mal, attack="additive",
            attack_kwargs=(("delta", delta),))
        cfg = diffusion.DiffusionConfig(
            step_size=paper_lsq.STEP_SIZE, aggregator="mm_tukey",
            agg_kwargs=(("c", 8.0),), byzantine=byz)
        _, h = diffusion.run_diffusion(
            grad_fn=prob.grad_fn(), combination=comb, config=cfg,
            w_star=prob.w_star, num_iters=iters, key=jax.random.key(0))
        rows.append((f"fig1_beyond/mm_tukey_c8/mal={n_mal}_d{delta:g}",
                     0.0, steady(np.asarray(h))))

    # ---- claim checks ----------------------------------------------------
    s = {r[0]: r[2] for r in rows}
    c1 = s["fig1_left/mean/delta=1000"] > 1e3 * s["fig1_left/mean/delta=0"]
    c2 = (s["fig1_right/median/mal=1"] < 1e-2
          and s["fig1_left/median/delta=0"]
          > 1.2 * s["fig1_left/mean/delta=0"])
    c3 = (s["fig1_left/mm_tukey/delta=1000"] < 1e-2
          and s["fig1_left/mm_tukey/delta=0"]
          < 1.25 * s["fig1_left/mean/delta=0"]
          and s["fig1_right/mm_tukey/mal=11"] < 5e-2)
    rows.append(("fig1/claim_C1_mean_breakdown", 0.0, float(c1)))
    rows.append(("fig1/claim_C2_median_robust_inefficient", 0.0, float(c2)))
    rows.append(("fig1/claim_C3_ref_robust_and_efficient", 0.0, float(c3)))
    return rows


if __name__ == "__main__":
    compat.enable_persistent_compilation_cache()
    for name, us, derived in main():
        print(f"{name},{us:.2f},{derived:.6g}")
