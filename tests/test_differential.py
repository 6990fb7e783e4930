"""Differential-testing harness: certifies the array engine everywhere.

Runs the ``reference`` Node-tree MCTS against ``ArrayMCTS`` in BOTH its
modes — scalar (one-at-a-time leaf evaluation, ``run_decision``) and
batched (lockstep pending-leaf rounds, ``run_decision_batch``) — over the
full configuration grid:

    UCB variant (paper | cp10 | sqrt2)
  × simulation policy (random | greedy)
  × reward mode (cost | binary)
  × 3 seeds
  × 2 model configs (a train MoE cell and a decode cell)

and asserts byte-identical trajectories: the same decision sequence, the
same per-decision best costs, and the same final best schedule.  This is
the parity coverage required before ``engine="array"`` became the default
in ``autotune`` / ``benchmarks.common.run_algo`` — any float drift, RNG
reordering, or tie-break change in the array engine fails loudly here.

The same grid also certifies the COLUMNAR PRICING KERNEL: a fourth leg
drives the batched engine over an MDP priced by the pre-columnar scalar
oracle (``AnalyticCostModel(columnar=False)``) while the other three legs
price through the column kernel with the small-batch dispatch disabled
(``columnar_min_batch=1`` — every batch, including every batch of one,
runs the vectorized kernel).  Identical trajectories mean the kernel
reproduces the scalar arithmetic bit-for-bit on every schedule the search
visits; any rounding difference would flip a UCB comparison somewhere in
the grid and fail loudly.

Engines sharing a pricing mode share a single ``CachedMDP`` per cell (the
two pricing modes get SEPARATE caches, so a cached value from one can
never mask a divergence in the other).  The cache is a pure memo
(identical values cached or not) — it only deduplicates pricing across
the grid's hundreds of trajectories, keeping the harness inside the
tier-1 budget.
"""
import json
import os

import pytest
from conftest import TABLE1_CELLS as CELLS
from conftest import make_cell_mdp

from repro.core.autotuner import autotune
from repro.core.engine import ArrayMCTS, CachedMDP
from repro.core.engine.batch import run_decision_batch
from repro.core.ensemble import ProTuner
from repro.core.mcts import MCTS, MCTSConfig

_SHARED = {}


def _mdp(cell: str, pricing: str = "columnar") -> CachedMDP:
    """One shared (cached) MDP per (cell, pricing mode) for the module.

    ``columnar`` forces every batch — every batch of ONE included —
    through the vectorized kernel (``columnar_min_batch=1``); ``scalar``
    is the pre-columnar per-plan oracle.  Separate caches per mode, so
    the memo cannot cross-feed values between the paths under test."""
    key = (cell, pricing)
    if key not in _SHARED:
        arch, shape_name = CELLS[cell]
        min_batch = 1 if pricing == "columnar" else None
        _SHARED[key] = CachedMDP(make_cell_mdp(
            arch, shape_name, pricing=pricing, columnar_min_batch=min_batch
        ))
    return _SHARED[key]


def _drive(tree, batched: bool = False, mdp=None):
    """Full tuning trajectory with one tree: every decision round, with
    tree reuse across rounds.  Returns everything an engine can diverge
    on."""
    actions, costs = [], []
    while not tree.done:
        if batched:
            res = run_decision_batch([tree], mdp)[0]
        else:
            res = tree.run_decision()
        actions.append(res.action)
        costs.append(res.best_cost)
        tree.advance_root(res.action)
    return actions, costs, tree.global_best, tree.global_best_state


# ---------------------------------------------------------------------------
# The grid
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cell", list(CELLS))
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("reward", ["cost", "binary"])
@pytest.mark.parametrize("simulation", ["random", "greedy"])
@pytest.mark.parametrize("ucb", ["paper", "cp10", "sqrt2"])
def test_engines_identical_across_grid(ucb, simulation, reward, seed, cell):
    mdp = _mdp(cell)
    cfg = MCTSConfig(
        ucb=ucb,
        simulation=simulation,
        reward_mode=reward,
        iters_per_decision=8,
        seed=seed,
    )
    ref = _drive(MCTS(mdp, cfg))
    arr = _drive(ArrayMCTS(mdp, cfg))
    bat = _drive(ArrayMCTS(mdp, cfg), batched=True, mdp=mdp)
    assert arr == ref, "scalar array engine diverged from reference"
    assert bat == ref, "batched array engine diverged from reference"
    # columnar-vs-scalar pricing leg: the batched engine over the
    # pre-columnar scalar oracle must reproduce the kernel-priced
    # trajectory exactly — bit-identical pricing, certified on the grid
    mdp_s = _mdp(cell, "scalar")
    sca = _drive(ArrayMCTS(mdp_s, cfg), batched=True, mdp=mdp_s)
    assert sca == ref, "scalar-oracle pricing diverged from columnar kernel"


# ---------------------------------------------------------------------------
# Ensemble level: the full ProTuner loop (root synchronization, winner
# selection, tree reuse) across all three engine modes, over the same
# UCB × rollout × reward × cell grid as the single-tree legs above.  A
# ProTuner ensemble always mixes random- and greedy-rollout trees, so the
# rollout axis picks the majority: "random" is 2 standard + 1 greedy tree,
# "greedy" is 1 standard + 2 greedy trees.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cell", list(CELLS))
@pytest.mark.parametrize("reward", ["cost", "binary"])
@pytest.mark.parametrize("rollout", ["random", "greedy"])
@pytest.mark.parametrize("ucb", ["paper", "cp10", "sqrt2"])
def test_ensemble_identical_across_engines(ucb, rollout, reward, cell):
    n_standard, n_greedy = (2, 1) if rollout == "random" else (1, 2)

    def run(**kw):
        res = ProTuner(
            _mdp(cell),
            n_standard=n_standard,
            n_greedy=n_greedy,
            mcts_config=MCTSConfig(ucb=ucb, reward_mode=reward,
                                   iters_per_decision=10),
            seed=3,
            **kw,
        ).run()
        return (
            res.plan,
            res.cost,
            [d["action"] for d in res.decisions],
            [d["best_cost"] for d in res.decisions],
            [d["winner_tree"] for d in res.decisions],
        )

    ref = run(engine="reference", cache=False)
    arr = run(engine="array", batch=False)
    bat = run(engine="array", batch=True)
    # run-controller leg (core/run_control.py): an UNINTERRUPTED run with
    # the controller mounted — checkpoint cadence firing into a sink —
    # must stay bit-identical to a controller-free run (the controller
    # reads a clock and pickles snapshots; it never touches search state)
    from repro.core.run_control import RunController

    sink = []
    con = run(engine="array", batch=True,
              controller=RunController(checkpoint_every=2,
                                       checkpoint_fn=sink.append))
    assert arr == ref
    assert bat == ref
    assert con == ref
    assert sink, "checkpoint cadence never fired"


# ---------------------------------------------------------------------------
# Benchmark tune cells: ``autotune(arch, shape, algo="mcts_1s", seed=0)`` is
# the one call the chip benchmark makes to get each cell's plan.  Plan,
# exact cost, eval count and decision trace are pinned to the values the
# search produced before the worker pool and learned-cost serving were
# removed (tests/data/benchmark_tune_cells.json), so any change to what the
# benchmark runs shows here first.
# ---------------------------------------------------------------------------
_PINNED = os.path.join(os.path.dirname(__file__), "data",
                       "benchmark_tune_cells.json")


@pytest.mark.parametrize("cell", ["granite-moe-1b-a400m/train_4k",
                                  "granite-3-2b/train_4k",
                                  "jamba2-3b/prefill_32k"])
def test_benchmark_tune_cells_plan_pinned(cell):
    with open(_PINNED) as f:
        want = json.load(f)[cell]
    res = autotune(*cell.split("/"), algo="mcts_1s", seed=0)
    # through JSON, as the pinned file holds it (tuples become lists)
    assert json.loads(json.dumps(res.plan.to_dict())) == want["plan"]
    assert repr(res.cost) == want["cost"]
    assert res.n_evals == want["n_evals"]
    assert res.decisions == want["decisions"]


# ---------------------------------------------------------------------------
# Service leg: the tuner daemon's COLD path (fresh store, warm-cell cache
# mounted, shared machinery) must reproduce one-shot autotune bit-for-bit
# — same plan, same exact cost, same decision trace — and the daemon's
# WARM answer must equal its own cold one after a store round-trip.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cell", list(CELLS))
def test_service_cold_path_identical_to_autotune(cell, tmp_path):
    from repro.service import TunerService

    arch, shape_name = CELLS[cell]
    kw = dict(algo="mcts_1s", seed=2, n_standard=2, n_greedy=1)
    ref = autotune(arch, shape_name, **kw)

    svc = TunerService(str(tmp_path / "store"), log=lambda *a: None)
    cold = svc.handle(dict(arch=arch, shape=shape_name, **kw))
    warm = svc.handle(dict(arch=arch, shape=shape_name, **kw))
    svc.shutdown()

    assert cold["served"] == "search" and warm["served"] == "store"
    for out in (cold, warm):
        assert out["result"]["plan"] == ref.plan.to_dict()
        assert out["result"]["cost"] == ref.cost
        assert out["result"]["decisions"] == ref.decisions


# ---------------------------------------------------------------------------
# Default flip: with the grid green, the array engine is the default
# ---------------------------------------------------------------------------
def test_array_engine_is_the_default():
    res = autotune(
        "granite-moe-1b-a400m", "train_4k", algo="mcts_1s", seed=0,
        n_standard=2, n_greedy=1,
    )
    assert res.engine == "array"
    assert res.cache_hits > 0  # shared transposition cache on by default

    tuner = ProTuner(_mdp("decode"), n_standard=1, n_greedy=0)
    assert tuner.engine == "array" and tuner.batch and tuner.cache is not None

    from benchmarks.common import run_algo

    res2, _ = run_algo("granite-moe-1b-a400m", "train_4k", "mcts_1s", seed=0,
                       n_standard=2, n_greedy=1)
    assert res2.engine == "array"


# ---------------------------------------------------------------------------
# Evolutionary + portfolio legs: fixed seed × both cells × exact analytic
# cost.  These pin (a) run-to-run determinism on fresh caches, (b) the
# eval-budget accounting contract — generation pricing hits the cost model
# exactly ONCE per unique plan, i.e. ``n_evals == cache.misses`` — and
# (c) that the portfolio's reported winner is the best member's result
# bit-for-bit.
# ---------------------------------------------------------------------------
def _fresh_cached(cell: str) -> CachedMDP:
    arch, shape_name = CELLS[cell]
    return CachedMDP(make_cell_mdp(arch, shape_name))


def _strip_wall(decisions):
    return [{k: v for k, v in d.items() if k != "wall_time_s"}
            for d in decisions]


@pytest.mark.parametrize("cell", list(CELLS))
@pytest.mark.parametrize("seed", [0, 1])
def test_evolve_deterministic_with_exact_eval_accounting(cell, seed):
    from repro.core.evolve import EvolutionarySearchBackend

    def run(mdp):
        return EvolutionarySearchBackend(population=16, generations=8).run(
            mdp, seed=seed
        )

    mdp_a, mdp_b = _fresh_cached(cell), _fresh_cached(cell)
    a, b = run(mdp_a), run(mdp_b)
    # run-to-run determinism on fresh caches: bit-identical everything
    assert a.plan == b.plan and a.cost == b.cost
    assert a.n_evals == b.n_evals and a.decisions == b.decisions
    # eval-budget accounting: each unique plan priced exactly once for the
    # whole run — the shared cache's misses ARE the model evals (revisits
    # are hits, and the final best-plan re-read is a hit too)
    assert a.n_evals == mdp_a.cache.misses == a.cache_misses
    assert a.cache_hits == mdp_a.cache.hits > 0
    # warm rerun over the SAME cache: zero new pricings, identical result
    # values (the cache is a pure memo — only eval counts change)
    c = run(mdp_a)
    assert c.plan == a.plan and c.cost == a.cost
    assert c.n_evals == a.n_evals  # no new evals: everything was cached


@pytest.mark.parametrize("cell", list(CELLS))
def test_portfolio_winner_is_best_member_bit_for_bit(cell):
    from repro.core.evolve import PortfolioBackend

    def run():
        mdp = _fresh_cached(cell)
        return PortfolioBackend().run(
            mdp, seed=0, n_standard=2, n_greedy=1
        ), mdp

    res, mdp = run()
    assert res.algo == "portfolio"
    assert [d["member"] for d in res.decisions] == [
        "evolve", "mcts_1s", "beam", "random"]
    winners = [d for d in res.decisions if d["winner"]]
    assert len(winners) == 1
    # the reported winner IS the best member's result, unmodified
    assert winners[0]["plan"] == res.plan.to_dict()
    assert winners[0]["cost"] == res.cost
    assert res.cost == min(d["cost"] for d in res.decisions)
    # unique-plan accounting across ALL members through the one shared cache
    assert res.n_evals == mdp.cache.misses == res.cache_misses
    # run-to-run determinism (wall times aside)
    res2, _ = run()
    assert res2.plan == res.plan and res2.cost == res.cost
    assert res2.n_evals == res.n_evals
    assert _strip_wall(res2.decisions) == _strip_wall(res.decisions)


def test_portfolio_shared_budget_skips_members_once_spent():
    from repro.core.evolve import PortfolioBackend

    mdp = _fresh_cached("decode")
    res = PortfolioBackend().run(
        mdp, seed=0, max_evals=40, n_standard=2, n_greedy=1
    )
    ran = [d["member"] for d in res.decisions]
    # evolve's first generations spend the budget; later members are
    # skipped entirely (not run with a zero budget)
    assert ran[0] == "evolve" and len(ran) < 4
    assert res.cost == min(d["cost"] for d in res.decisions)


@pytest.mark.parametrize("cell", list(CELLS))
def test_autotune_routes_evolve_and_portfolio(cell):
    arch, shape_name = CELLS[cell]
    r1 = autotune(arch, shape_name, algo="evolve", seed=0)
    r2 = autotune(arch, shape_name, algo="evolve", seed=0)
    assert r1.algo == "evolve" and r1.plan == r2.plan and r1.cost == r2.cost
    rp = autotune(arch, shape_name, algo="portfolio", seed=0,
                  n_standard=2, n_greedy=1)
    assert rp.algo == "portfolio"
    assert rp.cost <= r1.cost  # the portfolio contains an evolve member
