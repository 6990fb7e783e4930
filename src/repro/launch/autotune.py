"""ProTuner CLI: search for the best schedule of one (arch × shape) cell.

    python -m repro.launch.autotune --arch phi3.5-moe-42b-a6.6b --shape train_4k
    python -m repro.launch.autotune --arch deepseek-67b --shape decode_32k \
        --algo mcts_cost+real_1s --measure     # compile-in-the-loop
"""
from __future__ import annotations

import argparse
import json


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--algo", default="mcts_30s")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--measure", action="store_true",
                    help="real measurement (XLA compile) at root syncs")
    ap.add_argument("--measure-workers", type=int, default=None,
                    help="with --measure: fan measurements out to N "
                         "persistent fleet workers (core/measure_fleet) "
                         "instead of serial in-loop compiles")
    ap.add_argument("--budget-s", type=float, default=None)
    ap.add_argument("--engine", default="array",
                    choices=["reference", "array"],
                    help="MCTS tree engine (array = vectorized + shared "
                         "transposition cache; identical results)")
    ap.add_argument("--pricing", default=None,
                    choices=["scalar", "columnar", "jit"],
                    help="analytic pricing kernel: columnar (exact, "
                         "default), scalar (exact oracle replay), jit "
                         "(jax-jitted — ULP-level drift, versioned tag; "
                         "see cost_model.py)")
    ap.add_argument("--store", default=None,
                    help="PlanStore root directory: answer repeats from "
                         "disk, record this run, and (evolve/portfolio) "
                         "seed the population from stored plans")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)

    from repro.core.autotuner import autotune, make_mdp
    from repro.core.measure import make_measure_fn

    plan_store = None
    if args.store:
        from repro.service.store import PlanStore

        plan_store = PlanStore(args.store)
    measure_fn = measure_backend = fleet = None
    if args.measure and args.measure_workers:
        from repro.core.measure_fleet import MeasurementFleet

        fleet = MeasurementFleet(n_workers=args.measure_workers)
        measure_backend = fleet.bind(args.arch, args.shape, args.mesh)
    elif args.measure:
        measure_fn = make_measure_fn(args.arch, args.shape, args.mesh)
    try:
        res = autotune(
            args.arch,
            args.shape,
            algo=args.algo,
            mesh=args.mesh,
            seed=args.seed,
            measure_fn=measure_fn,
            measure_backend=measure_backend,
            time_budget_s=args.budget_s,
            engine=args.engine,
            pricing=args.pricing,
            plan_store=plan_store,
        )
    finally:
        if fleet is not None:
            fleet.shutdown()
    mdp = make_mdp(args.arch, args.shape, args.mesh)
    terms = mdp.cost_model.terms(res.plan)
    print(f"[autotune] {args.arch}×{args.shape} algo={res.algo}")
    if fleet is not None:
        print(f"[autotune] measurement fleet: {fleet.stats()}")
    if res.n_measure_failures:
        print(f"[autotune] WARNING: {res.n_measure_failures} candidate(s) "
              f"degraded to analytic cost after measurement failure")
    print(f"[autotune] best cost {res.cost*1e3:.2f} ms "
          f"(measured: {res.measured and f'{res.measured*1e3:.2f} ms'}) "
          f"evals={res.n_evals} measurements={res.n_measurements} "
          f"wall={res.wall_time_s:.1f}s")
    print(f"[autotune] plan: {json.dumps(res.plan.to_dict())}")
    print(f"[autotune] terms: compute={terms.compute_s*1e3:.2f}ms "
          f"memory={terms.memory_s*1e3:.2f}ms "
          f"collective={terms.collective_s*1e3:.2f}ms "
          f"dominant={terms.dominant} feasible={terms.feasible} "
          f"MFU={terms.details['mfu']:.3f}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(res.to_dict(), f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
