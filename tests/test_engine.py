"""Engine-layer tests: ArrayMCTS ↔ reference MCTS parity, lockstep
batched rounds, transposition cache exactness, and SearchBackend routing.

Parity is asserted EXACTLY (same action, same best_cost, same best_state
for a fixed seed): the array engine replicates the reference's RNG call
sequence and computes UCB with the same IEEE-754 operations, so any
drift is a real behavioral bug, not float noise."""
import dataclasses
import random

import pytest

from repro.core.autotuner import autotune, make_mdp
from repro.core.engine import ArrayMCTS, CachedMDP, TranspositionCache, make_tree
from repro.core.engine.backend import TABLE1, SearchBackend, resolve_backend
from repro.core.ensemble import ProTuner
from repro.core.mcts import MCTS, MCTSConfig

from conftest import MOE_TRAIN_CELL as CELL
from conftest import TABLE1_CELLS, make_cell_mdp


def _mdp():
    return make_mdp(*CELL)


# ---------------------------------------------------------------------------
# ArrayMCTS ↔ MCTS parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("ucb", ["paper", "cp10", "sqrt2"])
@pytest.mark.parametrize("simulation", ["random", "greedy"])
def test_array_matches_reference_single_decision(ucb, simulation):
    cfg = MCTSConfig(ucb=ucb, simulation=simulation,
                     iters_per_decision=32, seed=11)
    ref = MCTS(_mdp(), cfg)
    arr = ArrayMCTS(_mdp(), cfg)
    r, a = ref.run_decision(), arr.run_decision()
    assert (r.action, r.best_cost, r.best_state, r.iterations) == (
        a.action, a.best_cost, a.best_state, a.iterations
    )


def test_array_matches_reference_binary_reward():
    cfg = MCTSConfig(ucb="sqrt2", reward_mode="binary",
                     iters_per_decision=32, seed=2)
    r = MCTS(_mdp(), cfg).run_decision()
    a = ArrayMCTS(_mdp(), cfg).run_decision()
    assert (r.action, r.best_cost) == (a.action, a.best_cost)


def test_array_matches_reference_full_tuning_run():
    """Whole ensemble, all decision rounds, with tree reuse across rounds —
    and with the array side running through the shared cache (cached costs
    must be bit-identical, so the trajectories cannot diverge)."""
    cfg = MCTSConfig(iters_per_decision=16)
    r_ref = ProTuner(_mdp(), n_standard=2, n_greedy=1, mcts_config=cfg,
                     seed=5, engine="reference").run()
    r_arr = ProTuner(_mdp(), n_standard=2, n_greedy=1, mcts_config=cfg,
                     seed=5, engine="array").run()
    assert r_ref.plan == r_arr.plan
    assert r_ref.cost == r_arr.cost
    assert [d["action"] for d in r_ref.decisions] == [
        d["action"] for d in r_arr.decisions
    ]
    assert r_arr.cache_hits > 0  # ensemble trees share the cache


def test_array_engine_via_make_tree_and_autotune():
    assert isinstance(make_tree(_mdp(), MCTSConfig(), "array"), ArrayMCTS)
    assert isinstance(make_tree(_mdp(), MCTSConfig(), "reference"), MCTS)
    with pytest.raises(ValueError):
        make_tree(_mdp(), MCTSConfig(), "cuda")
    ra = autotune(*CELL, algo="mcts_1s", seed=0, n_standard=2, n_greedy=1,
                  engine="array")
    rb = autotune(*CELL, algo="mcts_1s", seed=0, n_standard=2, n_greedy=1,
                  engine="reference")
    assert ra.plan == rb.plan and ra.cost == rb.cost
    assert ra.engine == "array" and rb.engine == "reference"


# ---------------------------------------------------------------------------
# Batched leaf evaluation (lockstep pending-leaf rounds)
# ---------------------------------------------------------------------------
def test_batched_round_counts_each_evaluation_once():
    """Two identical-seed trees put the SAME pending leaf in every lockstep
    batch; the duplicate must be priced once — evaluation and cache
    counters must match the scalar one-at-a-time accounting exactly (no
    double count when a leaf is both expanded and simulated in the same
    batch by different trees)."""
    from repro.core.engine.batch import run_decision_batch

    cfg = MCTSConfig(iters_per_decision=16, seed=4)
    m_bat = CachedMDP(_mdp())
    res_bat = run_decision_batch(
        [ArrayMCTS(m_bat, cfg), ArrayMCTS(m_bat, cfg)], m_bat
    )
    m_seq = CachedMDP(_mdp())
    res_seq = [t.run_decision() for t in
               (ArrayMCTS(m_seq, cfg), ArrayMCTS(m_seq, cfg))]
    key = lambda r: (r.action, r.best_cost, r.best_state, r.iterations)
    assert [key(r) for r in res_bat] == [key(r) for r in res_seq]
    assert key(res_bat[0]) == key(res_bat[1])  # twins stayed in lockstep
    # each unique schedule priced exactly once, batched or not
    assert m_bat.mdp.cost_model.n_evals == m_bat.cache.misses
    assert m_bat.cache.misses == m_seq.cache.misses
    assert m_bat.cache.hits == m_seq.cache.hits
    assert m_bat.mdp.cost_model.n_evals == m_seq.mdp.cost_model.n_evals


def test_run_decision_counters_survive_batched_ensemble():
    """`n_evals` through a whole batched ensemble equals the unique misses
    the shared cache recorded — each batched evaluation counted once."""
    cfg = MCTSConfig(iters_per_decision=12)
    res = ProTuner(_mdp(), n_standard=3, n_greedy=1, mcts_config=cfg,
                   seed=2, engine="array", batch=True).run()
    assert res.n_evals == res.cache_misses
    res_scalar = ProTuner(_mdp(), n_standard=3, n_greedy=1, mcts_config=cfg,
                          seed=2, engine="array", batch=False).run()
    assert res.plan == res_scalar.plan and res.cost == res_scalar.cost
    assert res.n_evals == res_scalar.n_evals
    assert (res.cache_hits, res.cache_misses) == (
        res_scalar.cache_hits, res_scalar.cache_misses
    )


@pytest.mark.parametrize("cell", ["moe_train", "decode"])
@pytest.mark.parametrize("reward", ["cost", "binary"])
@pytest.mark.parametrize("ucb", ["paper", "cp10", "sqrt2"])
def test_lockstep_batch_identical_to_per_tree_loop(ucb, reward, cell):
    """A whole ensemble run with lockstep batched rounds (``batch=True``)
    equals the per-tree ``run_decision`` loop (``batch=False``): same
    plan, cost and decision trace, and — with the shared cache on — the
    same eval, hit and miss counters (first lookup of a state is a miss
    whatever the order, and the batch dedups what the loop would hit)."""
    cfg = MCTSConfig(ucb=ucb, reward_mode=reward, iters_per_decision=8)

    def run(batch):
        res = ProTuner(make_cell_mdp(*TABLE1_CELLS[cell]), n_standard=2,
                       n_greedy=1, mcts_config=cfg, seed=4, engine="array",
                       cache=True, batch=batch).run()
        return ((res.plan, res.cost, res.decisions),
                (res.n_evals, res.cache_hits, res.cache_misses))

    bat, loop = run(True), run(False)
    assert bat[0] == loop[0]
    assert bat[1] == loop[1]
    assert bat[1][0] == bat[1][2]  # each unique schedule priced once


# ---------------------------------------------------------------------------
# Transposition cache
# ---------------------------------------------------------------------------
def test_cache_returns_bit_identical_costs():
    raw, cached = _mdp(), CachedMDP(_mdp())
    rng = random.Random(0)
    states = [tuple(raw.space.random_actions(rng)) for _ in range(50)]
    for s in states:
        direct = raw.terminal_cost(s)
        assert cached.terminal_cost(s) == direct  # first: miss
        assert cached.terminal_cost(s) == direct  # second: hit
        prefix = s[: len(s) // 2]
        dp = raw.partial_cost(prefix)
        assert cached.partial_cost(prefix) == dp
        assert cached.partial_cost(prefix) == dp
    n_lookups = 4 * len(states)
    expect_misses = len(set(states)) + len({s[: len(s) // 2] for s in states})
    assert cached.cache.misses == expect_misses
    assert cached.cache.hits == n_lookups - expect_misses


def test_cache_shared_across_trees_saves_evals():
    """The cached ensemble must do strictly fewer cost-model evaluations
    than the uncached one, at identical results."""
    cfg = MCTSConfig(iters_per_decision=16)
    r_ref = ProTuner(_mdp(), n_standard=3, n_greedy=1, mcts_config=cfg,
                     seed=1, engine="reference").run()
    r_arr = ProTuner(_mdp(), n_standard=3, n_greedy=1, mcts_config=cfg,
                     seed=1, engine="array").run()
    assert r_arr.plan == r_ref.plan
    assert r_arr.n_evals < r_ref.n_evals
    assert r_arr.cache_hits == r_ref.n_evals - r_arr.n_evals


def test_pinned_worker_preload_chain_is_jax_free():
    """The measurement fleet's ``pick_mp_context`` preloads
    ``repro.core.measure_fleet`` into the forkserver on the promise that
    the chain is jax-free (forking a jax-threaded process can deadlock;
    jax lives behind lazy imports in ``cost_model``).  A top-level jax
    import sneaking into that chain would silently poison every fleet
    worker — keep it out."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src"
    ) + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, repro.core.measure_fleet; "
         "print('jax' in sys.modules)"],
        capture_output=True, text=True, env=env, check=True,
    )
    assert out.stdout.strip() == "False"


def test_cache_watermark_incremental_export():
    """The plan store's cell-sync seam: ``export_since(watermark)``
    returns exactly the entries added since the cursor — O(new entries),
    never a whole-table diff — and everything when there is no cursor."""
    c = TranspositionCache()
    c.terminal[(1,)] = 1.0
    wm = c.watermark()
    c.terminal[(2,)] = 2.0
    c.partial[(0,)] = 0.5
    entries = c.export_since(wm)
    assert entries == ({(2,): 2.0}, {(0,): 0.5})
    d = TranspositionCache()
    d.apply_export(entries)
    assert d.terminal == {(2,): 2.0} and d.partial == {(0,): 0.5}
    # nothing new since the current watermark -> empty incremental export
    assert c.export_since(c.watermark()) == ({}, {})
    # no cursor at all -> everything
    assert c.export_since(None) == (c.terminal, c.partial)


def test_cache_stats_and_merge():
    c1, c2 = TranspositionCache(), TranspositionCache()
    c1.terminal[(0, 1)] = 3.0
    c1.hits, c1.misses = 4, 1
    c2.terminal[(1, 1)] = 5.0
    c2.partial[(1,)] = 2.0
    c2.hits, c2.misses = 1, 2
    c1.merge(c2)
    assert c1.terminal == {(0, 1): 3.0, (1, 1): 5.0}
    assert c1.partial == {(1,): 2.0}
    assert (c1.hits, c1.misses) == (5, 3)
    assert c1.n_entries == 3
    assert 0 < c1.hit_rate < 1
    # pickling keeps mappings, resets counters (checkpoint protocol)
    import pickle

    c3 = pickle.loads(pickle.dumps(c1))
    assert c3.terminal == c1.terminal and c3.partial == c1.partial
    assert (c3.hits, c3.misses) == (0, 0)


# ---------------------------------------------------------------------------
# SearchBackend protocol
# ---------------------------------------------------------------------------
def test_resolve_backend_covers_all_algos():
    for algo in ["beam", "greedy", "random", "mcts", *TABLE1]:
        b = resolve_backend(algo)
        assert isinstance(b, SearchBackend), algo
    with pytest.raises(ValueError):
        resolve_backend("simulated_annealing")


def test_backends_run_through_protocol():
    for algo in ("beam", "greedy", "random"):
        res = resolve_backend(algo).run(_mdp(), seed=2)
        assert res.plan is not None and res.cost > 0
    res = resolve_backend("mcts_1s", engine="array").run(
        _mdp(), seed=2, n_standard=2, n_greedy=1
    )
    assert res.algo == "mcts_1s" and res.engine == "array"
    assert res.cache_hits > 0


def test_random_search_cached_backend_same_result():
    from repro.core.random_search import RandomBackend

    plain = RandomBackend(n_samples=64).run(_mdp(), seed=9)
    cached = RandomBackend(n_samples=64).run(_mdp(), seed=9, cache=True)
    assert plain.plan == cached.plan and plain.cost == cached.cost
