"""Declarative sweep harness: matrix spec → expand → run → measure via the
fleet → store stamped artifact rows.

The fig-7/fig-9/Table-1 style sweeps all share one shape — a cartesian
matrix of (cell × algo × budget …) over shared defaults — so, in the
style of matrix-benchmarking, the sweep IS a data file
(``benchmarks/sweeps/*.json``) and this module is the one runner:

    PYTHONPATH=src python -m benchmarks.sweep benchmarks/sweeps/fig9_budget.json
    ... --quick --measure stub --results /tmp/smoke    # CI smoke
    ... --measure real --workers 4                     # compile re-rank

Spec format (JSON — the perf-smoke CI env has no yaml)::

    {
      "name": "fig9_budget",
      "defaults": {"seed": 0, "noise_sigma": 0.25, ...},
      "matrix": {
        "cell": [["granite-3-2b", "train_4k"], ...],
        "algo": ["beam", "mcts_1s", "mcts_0.5s"]
      }
    }

Every expanded row gets a content-hash key over its settings; rows whose
key is already stored are skipped (resume a partial sweep for free, like
the measurement cache itself) unless ``--rerun``.  Phase 1 runs every
search; phase 2 fans ALL rows' best-plan measurements out in ONE
``MeasurementFleet.measure_many`` call (cache hits and single-flight
dedup included); phase 3 appends one JSONL row per cell to
``<results>/<name>.jsonl`` with the settings, engine provenance, wall
time, cost, and the measurement's retry/failure counters stamped.
``scripts/render_experiments.py`` renders the regression view over the
stored history.
"""
from __future__ import annotations

import argparse
import hashlib
import itertools
import json
import os
import sys
import time
from typing import Callable, List, Optional

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks.common import ENGINE_STAMP, run_algo  # noqa: E402

DEFAULT_RESULTS = os.path.join("experiments", "sweeps")

ROW_DEFAULTS = {
    "mesh": "single",
    "seed": 0,
    "noise_sigma": 0.0,
    "noise_seed": 0,
    "engine": "array",
    "budget_s": None,
    "n_standard": 15,
    "n_greedy": 1,
}


def load_spec(path: str) -> dict:
    with open(path) as f:
        spec = json.load(f)
    assert "name" in spec and "matrix" in spec, "spec needs name + matrix"
    return spec


def expand_spec(spec: dict) -> List[dict]:
    """Cartesian expansion of the matrix axes over the spec defaults.
    The ``cell`` axis is the (arch, shape) pair; every other axis value
    merges into the row settings under its axis name."""
    axes = spec["matrix"]
    names = sorted(axes)
    rows = []
    for combo in itertools.product(*(axes[n] for n in names)):
        row = dict(ROW_DEFAULTS)
        row.update(spec.get("defaults", {}))
        for name, value in zip(names, combo):
            if name == "cell":
                row["arch"], row["shape"] = value
            else:
                row[name] = value
        rows.append(row)
    return rows


def row_key(settings: dict) -> str:
    blob = json.dumps(settings, sort_keys=True, default=str)
    return hashlib.sha1(blob.encode()).hexdigest()[:12]


def stored_keys(path: str) -> set:
    """Keys of rows that are DONE — a stored row whose measurement failed
    (``measured_step_s: null`` with a failed outcome) does not count, so a
    transient fleet failure is retried on the next resume instead of being
    pinned forever."""
    keys = set()
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    row = json.loads(line)
                    key = row["key"]
                except (ValueError, KeyError):
                    continue  # a torn row never blocks a sweep
                measure = row.get("measure")
                if isinstance(measure, dict) and measure.get("failed"):
                    continue
                keys.add(key)
    return keys


def run_sweep(
    spec: dict,
    *,
    results_dir: str = DEFAULT_RESULTS,
    measure: str = "none",
    workers: int = 4,
    quick: bool = False,
    rerun: bool = False,
    fleet_kwargs: Optional[dict] = None,
    inject: Optional[Callable[[int, dict], None]] = None,
    log=print,
) -> List[dict]:
    """Run one sweep spec end to end; returns the newly stored rows.

    ``measure``: ``none`` (search only), ``stub`` (analytic stub records
    via the fleet — deterministic, XLA-free), ``real`` (subprocess XLA
    compiles via the fleet).  ``inject`` is the fault-injection hook the
    CI gate uses: called as ``inject(i, request)`` on each measurement
    request before dispatch (mutate ``request["extras"]`` in place).
    """
    assert measure in ("none", "stub", "real"), measure
    name = spec["name"]
    os.makedirs(results_dir, exist_ok=True)
    out_path = os.path.join(results_dir, f"{name}.jsonl")
    done = set() if rerun else stored_keys(out_path)

    rows = expand_spec(spec)
    if quick:
        rows = rows[:1]
    todo = []
    for settings in rows:
        key = row_key(settings)
        if key in done:
            continue
        todo.append((key, settings))
    log(f"[sweep:{name}] {len(rows)} row(s) expanded, "
        f"{len(rows) - len(todo)} already stored, {len(todo)} to run")
    if not todo:
        return []

    # phase 1: searches
    results = []
    for key, s in todo:
        t0 = time.perf_counter()
        res, _mdp = run_algo(
            s["arch"], s["shape"], s["algo"], seed=s["seed"],
            noise_sigma=s["noise_sigma"], noise_seed=s["noise_seed"],
            time_budget_s=s["budget_s"], n_standard=s["n_standard"],
            n_greedy=s["n_greedy"], engine=s["engine"],
        )
        wall = time.perf_counter() - t0
        results.append((key, s, res, wall))
        log(f"[sweep:{name}] {s['arch']}×{s['shape']} {s['algo']}: "
            f"cost {res.cost * 1e3:.2f} ms in {wall:.1f}s")

    # phase 2: one fan-out over every row's winning plan
    outcomes = [None] * len(results)
    fleet_stats = None
    if measure != "none":
        from repro.core.measure import make_request
        from repro.core.measure_fleet import MeasurementFleet

        fkw = dict(fleet_kwargs or {})
        if measure == "stub":
            from repro.core.measure_stub import stub_measure

            fkw.setdefault("target", stub_measure)
            fkw.setdefault(
                "cache_dir", os.path.join(results_dir, "measure_cache")
            )
        with MeasurementFleet(n_workers=workers, **fkw) as fleet:
            reqs = []
            for i, (key, s, res, wall) in enumerate(results):
                req = make_request(
                    s["arch"], s["shape"], s["mesh"], res.plan,
                    timeout=fleet.timeout,
                )
                if inject is not None:
                    inject(i, req)
                reqs.append(req)
            outcomes = fleet.measure_many(reqs)
            fleet_stats = fleet.stats()
        log(f"[sweep:{name}] fleet: {fleet_stats}")

    # phase 3: stamp + store
    new_rows = []
    with open(out_path, "a") as f:
        for (key, s, res, wall), out in zip(results, outcomes):
            row = {
                "sweep": name,
                "key": key,
                "settings": s,
                "engine": ENGINE_STAMP,
                "ts": time.time(),
                "cost": res.cost,
                "wall_s": round(wall, 3),
                "n_evals": res.n_evals,
                "n_measure_failures": res.n_measure_failures,
                "plan": res.plan.to_dict(),
                "measure_mode": measure,
                "measured_step_s": (
                    out.record["step_s"] if out is not None and out.ok
                    else None
                ),
                "measure": out.provenance() if out is not None else None,
                "fleet": fleet_stats,
            }
            f.write(json.dumps(row, default=str) + "\n")
            new_rows.append(row)
    log(f"[sweep:{name}] stored {len(new_rows)} row(s) → {out_path}")
    return new_rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("spec", help="path to a JSON matrix spec")
    ap.add_argument("--results", default=DEFAULT_RESULTS)
    ap.add_argument("--measure", default="none",
                    choices=["none", "stub", "real"])
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--quick", action="store_true",
                    help="run only the first expanded row (CI smoke)")
    ap.add_argument("--rerun", action="store_true",
                    help="re-run rows whose key is already stored")
    ap.add_argument("--list", action="store_true", dest="list_only",
                    help="print the expanded rows and exit")
    args = ap.parse_args(argv)
    spec = load_spec(args.spec)
    if args.list_only:
        for s in expand_spec(spec):
            print(row_key(s), json.dumps(s, sort_keys=True, default=str))
        return 0
    run_sweep(
        spec,
        results_dir=args.results,
        measure=args.measure,
        workers=args.workers,
        quick=args.quick,
        rerun=args.rerun,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
