"""Benchmark driver: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run              # full suite
    PYTHONPATH=src python -m benchmarks.run --quick      # 4-cell smoke
    PYTHONPATH=src python -m benchmarks.run --measure    # + compile-in-loop

Emits ``name,us_per_call,derived`` CSV lines; detailed JSON artifacts land
in experiments/bench/.
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--measure", action="store_true",
                    help="include compile-in-the-loop cost+real runs")
    ap.add_argument("--only", default=None,
                    help="comma list: roofline,fig7,fig8,fig9,fig45,table1,"
                         "search,fig12,noise,engine")
    args = ap.parse_args(argv)

    from benchmarks import (engine_throughput, fig7_cost, fig8_exec,
                            fig9_budget, fig12_partial_cost, fig45_ensemble,
                            noise_robustness, roofline, search_time,
                            table1_configs)
    from benchmarks.common import SUITE

    cells = SUITE[:4] if args.quick else None
    seeds = (0,) if args.quick else (0, 1)
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    t0 = time.time()
    print("name,us_per_call,derived")
    if want("roofline"):
        print("# --- §Roofline (from the compiled dry-run) ---")
        roofline.main("single")
        roofline.main("multi")
    if want("fig7"):
        print("# --- Fig 7: minimum cost found (normalized) ---")
        fig7_cost.main(cells=cells, seeds=seeds)
    if want("fig8"):
        print("# --- Fig 8: execution time of chosen schedules ---")
        fig8_exec.main(cells=cells, seeds=seeds[:2], measure=args.measure)
    if want("table1"):
        print("# --- Table 1: MCTS configuration sweep ---")
        table1_configs.main(cells=cells, seeds=seeds[:2])
    if want("fig45"):
        print("# --- Fig 4/5: ensemble composition (standard vs greedy) ---")
        fig45_ensemble.main(seeds=seeds[:2])
    if want("fig9"):
        print("# --- Fig 9: fixed wall-clock budget ---")
        fig9_budget.main(cells=cells[:4] if cells else None,
                         budget_s=6.0 if args.quick else 12.0)
    if want("search"):
        print("# --- §5.3: search time breakdown ---")
        search_time.main()
    if want("engine"):
        print("# --- engine: array MCTS + transposition cache throughput ---")
        if args.quick:
            engine_throughput.main(iters=96, n_standard=7, publish=False,
                                   reps=2)
        else:
            engine_throughput.main()
    if want("fig12"):
        print("# --- Fig 1/2 (§3): cost models on partial schedules ---")
        fig12_partial_cost.main()
    if want("noise"):
        print("# --- beyond-paper: noise robustness ablation ---")
        noise_robustness.main(seeds=seeds)
    print(f"# total bench wall time: {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
