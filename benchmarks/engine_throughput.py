"""Engine throughput: reference Node-tree MCTS vs the vectorized array
engine — one-at-a-time vs batched leaf evaluation vs the columnar kernel.

Runs the Table-1 ensemble protocol (384 iterations/decision, 15 standard
+ 1 greedy tree) on two representative cells with four engine legs — the
searches are behaviorally identical for the same seeds (certified by
``tests/test_differential.py``), so this is a pure implementation
comparison:

* ``reference``      — paper-faithful Node trees, scalar pricing, no cache;
* ``array_scalar``   — the PR-1 array engine: flat arrays + shared
  transposition cache, but one-at-a-time leaf evaluation;
* ``array_batched``  — the PR-2 engine: lockstep pending-leaf rounds with
  batched terminal-cost evaluation, miss batches priced by the scalar
  per-plan replay (``AnalyticCostModel(columnar=False)``);
* ``array``          — the default engine: the same lockstep rounds with
  miss batches priced by the COLUMNAR roofline kernel
  (``PlanColumns`` + ``_terms_columnar``, one vectorized pass per batch).

A cost-kernel microbenchmark rides along per cell (``kernel_*`` columns):
one deduplicated batch of random unique plans priced scalar-batched vs
columnar, isolating the kernel win from engine bookkeeping — at Table-1
miss-batch sizes the column math clears the scalar replay by whatever the
end-to-end legs can't show once cache hit rates pass 99%.  A second
microbench (``kernel_jit_*`` columns) compares all THREE pricing paths —
scalar replay, columnar kernel, jax-jitted kernel — on the
``cost_columns`` seam (pre-encoded ``PlanColumns``, so shared dedup/encode
overhead is out of the picture) at batch sizes 1/16/256: batch 1 shows the
jax dispatch floor that keeps ``JIT_MIN_BATCH`` above 1, batch 256 is the
generation-sized burst where the jitted kernel must beat the columnar one.

All legs run in one process; the timings are host-CPU wall clock.

Gate policy (``--check``): gates split into DETERMINISTIC ones (identical
results across legs — exactly reproducible for fixed seeds, so any miss
is a real regression and fails immediately)
and WALL-CLOCK ratio ones (speedups, kernel crossovers — subject to CI
cgroup throttling bursts that can halve a leg).  A wall-clock miss
triggers ONE full re-run of the benchmark: the check fails only if a
wall-clock gate misses on both runs (or a deterministic gate misses at
all).  This keeps the flake rate quadratically small without ever
loosening the deterministic guarantees.

Reported per cell: iterations/sec per leg, cache hits/misses, and three
speedups — ``speedup`` (columnar array vs reference, the end-to-end win),
``speedup_batched_vs_scalar`` (batching leaf evaluation over PR-1), and
``speedup_columnar_vs_batched`` (the columnar kernel over the scalar
replay, end-to-end).  ``--check`` enforces three things on the decode
headline cell: the array engine beats the reference, all legs produce
identical results, and the columnar kernel does not regress the hot path
— the isolated kernel microbench must beat the scalar replay outright,
and the end-to-end columnar leg must clear a catastrophic-regression
floor (per-leg end-to-end ratios swing wildly under CI cgroup
throttling; the microbench, measured back-to-back, is where a silent
kernel regression cannot hide).

    PYTHONPATH=src python -m benchmarks.engine_throughput
    PYTHONPATH=src python -m benchmarks.engine_throughput --quick --check
"""
from __future__ import annotations

import argparse
import random
import sys
import time

from benchmarks.common import ENGINE_STAMP, csv_line, emit
from repro.core.autotuner import make_mdp
from repro.core.cost_model import AnalyticCostModel
from repro.core.ensemble import ProTuner
from repro.core.mcts import MCTSConfig

# headline first: the decode cell's compact space is where the shared
# cache pays off hardest (96%+ hit rate at Table-1 budgets) and where
# selection/backprop — what the batched driver restructures — dominate
CELLS = [
    ("granite-3-2b", "decode_32k"),
    ("granite-moe-1b-a400m", "train_4k"),
]

# the end-to-end columnar-vs-batched gate tolerance: at 99%+ cache hit
# rates pricing is a sliver of wall time, so the leg ratio is parity plus
# scheduler noise — and on cgroup-throttled CI runners a throttling burst
# can halve a whole leg (observed: identical code measured anywhere from
# 0.62x to 1.11x).  The tight regression catch is therefore the kernel
# microbench (4-9x margin, adjacent measurements, robust under
# throttling); the leg floor only catches a CATASTROPHIC end-to-end
# regression (e.g. the kernel engaging where it loses badly).
COLUMNAR_LEG_FLOOR = 0.5
KERNEL_BATCH = 256  # microbench batch: a Table-1 first-round miss burst
# kernel_jit microbench grid: the jax dispatch floor (1), the columnar
# dispatch threshold (16), and a generation/miss-burst width (256) — the
# batch the jit-vs-columnar gate runs at
KERNEL_JIT_BATCHES = (1, 16, 256)


def run_ensemble(cell, engine: str, *, iters: int, n_standard: int,
                 n_greedy: int, seed: int = 0, cache=None, batch=None,
                 columnar: bool = True):
    """One full tuning run; returns (TuneResult, iterations, wall_s).
    ``columnar=False`` flips the cell's cost model to the pre-columnar
    scalar replay (values bit-identical; only the pricing path changes).
    Repetition/noise
    handling lives in ``bench_cell`` (rotating best-of-reps), not here."""
    arch, shape = cell
    mdp = make_mdp(arch, shape)
    mdp.cost_model.columnar = columnar
    cfg = MCTSConfig(iters_per_decision=iters, seed=seed)
    tuner = ProTuner(mdp, n_standard=n_standard, n_greedy=n_greedy,
                     mcts_config=cfg, seed=seed, engine=engine,
                     cache=cache, batch=batch)
    t0 = time.perf_counter()
    res = tuner.run()
    wall = time.perf_counter() - t0
    total_iters = iters * (n_standard + n_greedy) * len(res.decisions)
    return res, total_iters, wall


def bench_kernel(cell, *, n_plans: int = KERNEL_BATCH, reps: int = 5) -> dict:
    """The isolated pricing comparison: one deduplicated batch of random
    unique plans, scalar-batched replay vs the columnar kernel.  Values
    are asserted identical; the ratio is the kernel's clean win."""
    arch, shape = cell
    mdp = make_mdp(arch, shape)
    space = mdp.space
    rng = random.Random(0)
    seen, plans = set(), []
    while len(plans) < n_plans:
        p = space.random_plan(rng)
        if p not in seen:
            seen.add(p)
            plans.append(p)
    cfg, shp, mesh = space.cfg, space.shape, space.mesh
    scalar = AnalyticCostModel(cfg, shp, mesh, columnar=False)
    columnar = AnalyticCostModel(cfg, shp, mesh)  # default: kernel + dispatch
    assert scalar.cost_batch(plans) == columnar.cost_batch(plans)  # warm + certify
    t_s = min(
        _timed(lambda: scalar.cost_batch(plans)) for _ in range(reps)
    )
    t_c = min(
        _timed(lambda: columnar.cost_batch(plans)) for _ in range(reps)
    )
    return {
        "kernel_batch": len(plans),
        "kernel_scalar_us_per_plan": t_s / len(plans) * 1e6,
        "kernel_columnar_us_per_plan": t_c / len(plans) * 1e6,
        "kernel_speedup": t_s / t_c,
    }


def bench_kernel_jit(cell, *, reps: int = 5) -> dict:
    """Three-way pricing-path comparison on the ``cost_columns`` seam:
    scalar replay vs columnar kernel vs jax-jitted kernel over the SAME
    pre-encoded ``PlanColumns`` batches at sizes 1/16/256 (adjacent
    best-of-reps measurements, dedup/encode excluded — the cleanest view
    of each kernel's own cost).  The jitted model is warmed first so XLA
    compiles never land in a timed rep.  Values are certified along the
    way: scalar == columnar exactly, jit within JIT_RTOL."""
    from repro.core.cost_model import JIT_RTOL, PlanColumns

    arch, shape = cell
    mdp = make_mdp(arch, shape)
    space = mdp.space
    rng = random.Random(0)
    seen, plans = set(), []
    while len(plans) < max(KERNEL_JIT_BATCHES):
        p = space.random_plan(rng)
        if p not in seen:
            seen.add(p)
            plans.append(p)
    cfg, shp, mesh = space.cfg, space.shape, space.mesh
    # min_batch=1 on the kernel models so batch 1 really measures the
    # kernels (the production dispatch would route it to scalar replay)
    models = {
        "scalar": AnalyticCostModel(cfg, shp, mesh, columnar=False),
        "columnar": AnalyticCostModel(cfg, shp, mesh, columnar_min_batch=1),
        "jit": AnalyticCostModel(cfg, shp, mesh, pricing="jit",
                                 columnar_min_batch=1),
    }
    out = {"kernel_jit_batches": list(KERNEL_JIT_BATCHES)}
    for b in KERNEL_JIT_BATCHES:
        cols = PlanColumns.from_plans(plans[:b])
        vals = {}
        for name, m in models.items():
            vals[name] = m.cost_columns(cols)  # warm: ctx + jit compile
            t = min(_timed(lambda: m.cost_columns(cols)) for _ in range(reps))
            out[f"kernel_{name}_us_per_plan_b{b}"] = t / b * 1e6
        assert vals["scalar"] == vals["columnar"]
        import numpy as _np
        _np.testing.assert_allclose(
            _np.asarray(vals["jit"]), _np.asarray(vals["columnar"]),
            rtol=JIT_RTOL, atol=0.0)
        out[f"kernel_jit_vs_columnar_b{b}"] = (
            out[f"kernel_columnar_us_per_plan_b{b}"]
            / out[f"kernel_jit_us_per_plan_b{b}"])
        out[f"kernel_jit_vs_scalar_b{b}"] = (
            out[f"kernel_scalar_us_per_plan_b{b}"]
            / out[f"kernel_jit_us_per_plan_b{b}"])
    return out


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


LEGS = [
    # leg key -> run_ensemble overrides; bench_cell round-robins the legs
    # ACROSS reps (leg order rotates within each rep) so slow temporal
    # drift in machine load — the dominant noise source on shared runners
    # — cannot systematically bias any one leg
    ("reference", dict(engine="reference", columnar=False)),
    ("array_scalar", dict(engine="array", batch=False, columnar=False)),
    ("array_batched", dict(engine="array", columnar=False)),
    ("array", dict(engine="array")),
]


def bench_cell(cell, *, iters: int, n_standard: int, n_greedy: int,
               reps: int = 3) -> dict:
    """One cell's full leg matrix."""
    out = {"cell": "x".join(cell), "iters_per_decision": iters,
           "n_trees": n_standard + n_greedy,
           # the engine that produced the headline (array_*) columns — the
           # repo default; render_experiments.py reports this
           "engine": ENGINE_STAMP}

    best = {}
    for rep in range(max(reps, 1)):
        for i in range(len(LEGS)):
            name, kw = LEGS[(i + rep) % len(LEGS)]
            got = run_ensemble(cell, iters=iters, n_standard=n_standard,
                               n_greedy=n_greedy, **kw)
            if name not in best or got[2] < best[name][2]:
                best[name] = got

    res_ref, it_ref, wall_ref = best["reference"]
    out["reference_wall_s"] = wall_ref
    out["reference_iters_per_sec"] = it_ref / wall_ref
    out["reference_evals"] = res_ref.n_evals

    res_sca, it_sca, wall_sca = best["array_scalar"]
    out["array_scalar_wall_s"] = wall_sca
    out["array_scalar_iters_per_sec"] = it_sca / wall_sca

    res_bat, it_bat, wall_bat = best["array_batched"]
    out["array_batched_wall_s"] = wall_bat
    out["array_batched_iters_per_sec"] = it_bat / wall_bat

    res_arr, it_arr, wall_arr = best["array"]
    out["array_wall_s"] = wall_arr
    out["array_iters_per_sec"] = it_arr / wall_arr
    out["array_evals"] = res_arr.n_evals
    out["cache_hits"] = res_arr.cache_hits
    out["cache_misses"] = res_arr.cache_misses
    out["cache_hit_rate"] = res_arr.cache_hits / max(
        res_arr.cache_hits + res_arr.cache_misses, 1)
    out["evals_saved"] = res_ref.n_evals - res_arr.n_evals
    out["speedup"] = out["array_iters_per_sec"] / out["reference_iters_per_sec"]
    out["speedup_batched_vs_scalar"] = (
        out["array_batched_iters_per_sec"] / out["array_scalar_iters_per_sec"])
    out["speedup_columnar_vs_batched"] = (
        out["array_iters_per_sec"] / out["array_batched_iters_per_sec"])
    out["same_result"] = (
        res_ref.plan == res_sca.plan == res_bat.plan == res_arr.plan
        and res_ref.cost == res_sca.cost == res_bat.cost == res_arr.cost
        and [d["action"] for d in res_ref.decisions]
        == [d["action"] for d in res_sca.decisions]
        == [d["action"] for d in res_bat.decisions]
        == [d["action"] for d in res_arr.decisions])
    out.update(bench_kernel(cell))
    out.update(bench_kernel_jit(cell))

    name = out["cell"]
    csv_line(f"engine_throughput[{name}][reference]", wall_ref * 1e6,
             f"{out['reference_iters_per_sec']:.0f} it/s")
    csv_line(f"engine_throughput[{name}][array+scalar]", wall_sca * 1e6,
             f"{out['array_scalar_iters_per_sec']:.0f} it/s")
    csv_line(f"engine_throughput[{name}][array+batched]", wall_bat * 1e6,
             f"{out['array_batched_iters_per_sec']:.0f} it/s")
    csv_line(f"engine_throughput[{name}][array+columnar]", wall_arr * 1e6,
             f"{out['array_iters_per_sec']:.0f} it/s")
    csv_line(f"engine_throughput_kernel[{name}]",
             out["kernel_columnar_us_per_plan"],
             f"{out['kernel_speedup']:.2f}x columnar-vs-scalar on "
             f"{out['kernel_batch']}-plan miss batches "
             f"({out['kernel_scalar_us_per_plan']:.1f} -> "
             f"{out['kernel_columnar_us_per_plan']:.1f} us/plan)")
    csv_line(f"engine_throughput_kernel_jit[{name}]",
             out["kernel_jit_us_per_plan_b256"],
             "; ".join(
                 f"b={b}: scalar {out[f'kernel_scalar_us_per_plan_b{b}']:.1f}"
                 f" / columnar {out[f'kernel_columnar_us_per_plan_b{b}']:.1f}"
                 f" / jit {out[f'kernel_jit_us_per_plan_b{b}']:.1f} us/plan"
                 f" (jit {out[f'kernel_jit_vs_columnar_b{b}']:.2f}x vs"
                 f" columnar)"
                 for b in KERNEL_JIT_BATCHES))
    csv_line(f"engine_throughput_speedup[{name}]", 0.0,
             f"{out['speedup']:.1f}x vs reference; "
             f"{out['speedup_batched_vs_scalar']:.2f}x batched-vs-scalar; "
             f"{out['speedup_columnar_vs_batched']:.2f}x columnar-vs-batched; "
             f"cache_hits={out['cache_hits']}; "
             f"hit_rate={out['cache_hit_rate']:.3f}; "
             f"evals_saved={out['evals_saved']}; same={out['same_result']}")
    return out


def check_rows(rows) -> tuple:
    """Evaluate the CI gates on benchmarked rows.  Returns
    ``(hard, soft)`` failure-message lists: ``hard`` gates are
    DETERMINISTIC (identical plans/costs/decisions across legs — exactly
    reproducible for fixed seeds, never retried), ``soft`` gates are
    wall-clock ratios (retried once by the ``--check`` driver before
    failing; see the module docstring)."""
    hard, soft = [], []
    for row in rows:
        if not row["same_result"]:
            hard.append(f"{row['cell']}: engines diverged")
    r0 = rows[0]
    # --- wall-clock ratio gates (retry-once) ---
    if r0["speedup"] < 1.0:
        soft.append(
            f"{r0['cell']}: array engine slower than reference "
            f"({r0['speedup']:.2f}x)")
    if r0["kernel_speedup"] < 1.0:
        soft.append(
            f"{r0['cell']}: columnar kernel slower than the "
            f"scalar replay on {r0['kernel_batch']}-plan batches "
            f"({r0['kernel_speedup']:.2f}x)")
    b = max(KERNEL_JIT_BATCHES)
    if r0[f"kernel_jit_vs_columnar_b{b}"] < 1.0:
        soft.append(
            f"{r0['cell']}: jitted kernel slower than columnar at "
            f"batch {b} ({r0[f'kernel_jit_vs_columnar_b{b}']:.2f}x)")
    if r0["speedup_columnar_vs_batched"] < COLUMNAR_LEG_FLOOR:
        soft.append(
            f"{r0['cell']}: columnar leg regressed end-to-end "
            f"({r0['speedup_columnar_vs_batched']:.2f}x < "
            f"{COLUMNAR_LEG_FLOOR})")
    return hard, soft


def main(iters: int = 384, n_standard: int = 15, n_greedy: int = 1,
         publish: bool = True, reps: int = 3) -> list:
    rows = [bench_cell(c, iters=iters, n_standard=n_standard,
                       n_greedy=n_greedy, reps=reps)
            for c in CELLS]
    if publish:  # scaled-down (--quick / CI-gate) runs must not overwrite
        emit(rows, "engine_throughput")  # the published Table-1 artifact
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="scaled-down budgets (96 iters, 7+1 trees)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless, on the decode cell: the array "
                         "engine beats reference, the columnar kernel "
                         "holds the hot path (leg parity + microbench "
                         "win), and all legs agree (CI gate)")
    args = ap.parse_args()
    kw = (dict(iters=96, n_standard=7, publish=False, reps=2)
          if args.quick else {})
    rows = main(**kw)
    r = rows[0]
    print(f"# headline {r['cell']}: {r['speedup']:.2f}x vs reference, "
          f"{r['speedup_columnar_vs_batched']:.2f}x columnar-vs-batched "
          f"({r['array_batched_iters_per_sec']:.0f} -> "
          f"{r['array_iters_per_sec']:.0f} it/s), kernel "
          f"{r['kernel_speedup']:.2f}x on {r['kernel_batch']}-plan batches, "
          f"cache hits {r['cache_hits']}, evals saved {r['evals_saved']}, "
          f"identical result: {r['same_result']}")
    if args.check:
        hard, soft = check_rows(rows)
        if not hard and soft:
            # Retry-once-on-miss: wall-clock ratio gates are subject to CI
            # throttling bursts, so one miss buys exactly one full re-run;
            # only a second miss fails.  Deterministic gates (hard) never
            # retry — a miss there is a real regression.
            print("# wall-clock gate miss, retrying once: "
                  + "; ".join(soft))
            rows = main(**kw)
            hard, soft = check_rows(rows)
        bad = hard + soft
        if bad:
            print("# CHECK FAILED: " + "; ".join(bad))
            sys.exit(1)
        print("# check passed: array >= reference, columnar kernel >= "
              "scalar replay, jit kernel >= columnar at batch "
              f"{max(KERNEL_JIT_BATCHES)}, columnar leg holds the batched "
              "leg, all legs identical on the decode cell")
