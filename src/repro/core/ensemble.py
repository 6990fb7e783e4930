"""ProTuner ensemble: N standard + M greedy MCTSes with synchronized roots
(paper §4.1/§4.2, pseudocode Fig. 6).

Every decision round each tree spends its budget from the shared current
root; the winning next root is the tree whose subtree found the best
complete schedule — by cost model, or by **real measurement** of each
tree's best candidate when ``measure_fn`` is given (``mcts_cost+real_*``).
All trees then advance to the same child (keeping their subtrees).

Engine layer: trees are built by ``repro.core.engine.make_tree`` —
``engine="array"`` (the default: flat-array ``ArrayMCTS``, identical
results, batched UCB) or ``engine="reference"`` (the paper-faithful
``Node`` trees, kept as the oracle).  All trees run in this process and
price on the exact analytic cost.  With ``cache=True`` (the default for
the array engine) all trees share one ``TranspositionCache`` so a schedule
any tree has ever priced is never re-evaluated — across trees *and* across
decision rounds.  With ``batch=True`` (also the array default) decision
rounds run the trees in LOCKSTEP: each step's K concurrent simulations
queue their pending leaves into one ``terminal_cost_batch`` call
(``repro.core.engine.batch``) — results are identical to the per-tree
loop, and with the cache on so are the aggregate cache/eval counters
(uncached, in-batch dedup can only lower ``n_evals``).
"""
from __future__ import annotations

import dataclasses
import logging
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from repro.core.engine import CachedMDP, TranspositionCache, make_tree
from repro.core.engine.array_mcts import ArrayMCTS
from repro.core.engine.batch import run_decision_batch
from repro.core.mcts import MCTSConfig
from repro.core.mdp import ScheduleMDP, State
from repro.core.space import SchedulePlan

# ProTuner.snapshot() schema version (round-boundary checkpoints; bump on
# any change to the snapshot dict's shape so stale checkpoints are ignored
# instead of mis-restored)
SNAPSHOT_VERSION = 1

logger = logging.getLogger(__name__)


@dataclass
class TuneResult:
    plan: SchedulePlan
    cost: float  # EXACT analytic cost of the final schedule
    measured: Optional[float]  # real-measured step time (if measuring)
    n_evals: int  # cost-model evaluations
    n_measurements: int
    wall_time_s: float
    decisions: List[dict] = field(default_factory=list)
    algo: str = ""
    engine: str = "reference"
    cache_hits: int = 0
    cache_misses: int = 0
    # run provenance: ``interrupted`` for a RunController run cut short
    stats: dict = field(default_factory=dict)
    # candidates whose real measurement failed and were re-ranked by their
    # exact analytic cost instead (mcts_cost+real_* graceful degradation)
    n_measure_failures: int = 0
    # served from the persistent PlanStore (repro.service) without a
    # search — n_evals is 0 and decisions are the stored run's
    from_store: bool = False

    def to_dict(self):
        d = dataclasses.asdict(self)
        d["plan"] = self.plan.to_dict()
        return d


class ProTuner:
    def __init__(
        self,
        mdp: ScheduleMDP,
        *,
        n_standard: int = 15,
        n_greedy: int = 1,
        mcts_config: MCTSConfig = MCTSConfig(),
        measure_fn: Optional[Callable[[SchedulePlan], float]] = None,
        measure_backend=None,
        seed: int = 0,
        engine: str = "array",
        cache: Optional[bool] = None,
        batch: Optional[bool] = None,
        controller=None,
        resume: Optional[dict] = None,
    ):
        # measure_backend: a fleet-bound FleetMeasure (core/measure_fleet).
        # It is callable with the same plan -> seconds contract, so it can
        # stand in for measure_fn wholesale; when present, candidate
        # batches additionally prefetch through its measure_plans fan-out
        # so the re-rank blocks on ONE round trip instead of N serial
        # compiles.
        self.measure_backend = measure_backend
        if measure_fn is None and measure_backend is not None:
            measure_fn = measure_backend
        self.measure_fn = measure_fn
        self.engine = engine
        if cache is None:
            cache = engine == "array"
        if batch is None:
            batch = engine == "array"
        self.batch = batch
        if cache and not isinstance(mdp, CachedMDP):
            mdp = CachedMDP(mdp)
        self.mdp = mdp
        self.cache: Optional[TranspositionCache] = (
            mdp.cache if isinstance(mdp, CachedMDP) else None
        )
        self.trees = []
        self.greedy_flags: List[bool] = []
        for i in range(n_standard):
            cfg = dataclasses.replace(mcts_config, simulation="random", seed=seed * 1000 + i)
            self.trees.append(make_tree(mdp, cfg, engine))
            self.greedy_flags.append(False)
        for i in range(n_greedy):
            cfg = dataclasses.replace(
                mcts_config, simulation="greedy", seed=seed * 1000 + 500 + i
            )
            self.trees.append(make_tree(mdp, cfg, engine))
            self.greedy_flags.append(True)
        self._measure_cache: Dict[State, float] = {}
        self._measure_failed: set = set()  # states re-ranked by analytic cost
        self.n_measurements = 0
        self.n_measure_failures = 0
        # round-boundary run control (core/run_control.py): deadline /
        # cancel / checkpoint hooks.  ``decisions`` lives on the instance
        # so snapshot()/restore round-trip the full decision trace.
        self.controller = controller
        self.decisions: List[dict] = []
        if resume is not None:
            self._restore(resume)

    # -- round-boundary checkpointing (core/run_control.py) ------------
    def snapshot(self) -> dict:
        """Everything a fresh ``ProTuner`` (built from the same request)
        needs to replay the remaining rounds bit-identically: the live
        trees (each carries its own ``random.Random`` and stat arrays), the
        decision trace, and the measurement memo.  The caller pickles the
        dict — the trees' shared ``mdp`` (and cache) dedups inside one
        ``dumps``."""
        return {
            "version": SNAPSHOT_VERSION,
            "engine": self.engine,
            "round": len(self.decisions),
            "decisions": list(self.decisions),
            "trees": self.trees,
            "measure_cache": dict(self._measure_cache),
            "measure_failed": set(self._measure_failed),
            "n_measurements": self.n_measurements,
            "n_measure_failures": self.n_measure_failures,
        }

    def _restore(self, snap: dict) -> None:
        """Adopt a ``snapshot()`` (typically pickle-round-tripped through
        the plan store's checkpoint tier).  A snapshot that doesn't match
        this run's shape is ignored — the run starts fresh, which is
        always correct, just slower."""
        trees = snap.get("trees") if isinstance(snap, dict) else None
        if (
            not isinstance(snap, dict)
            or snap.get("version") != SNAPSHOT_VERSION
            or not trees
            or len(trees) != len(self.trees)
            or snap.get("engine") != self.engine
        ):
            logger.warning("checkpoint does not match this run; starting fresh")
            return
        old_mdp = trees[0].mdp
        if isinstance(old_mdp, CachedMDP) and isinstance(self.mdp, CachedMDP):
            # warm entries priced before the interrupt survive it; a pure
            # memo of exact values never changes plan/cost/decisions
            self.mdp.cache.merge(old_mdp.cache)
        for t in trees:
            t.mdp = self.mdp  # reattach this run's (shared) mdp + cache
        self.trees = trees
        self.decisions = list(snap["decisions"])
        self._measure_cache = dict(snap["measure_cache"])
        self._measure_failed = set(snap["measure_failed"])
        self.n_measurements = snap["n_measurements"]
        self.n_measure_failures = snap["n_measure_failures"]

    # ------------------------------------------------------------------
    def _degrade(self, state: State, why: str) -> float:
        """A failed measurement must not kill the run: re-rank this
        candidate by its EXACT analytic cost, count it, and keep going."""
        self.n_measure_failures += 1
        t = self.mdp.terminal_cost(state)
        self._measure_cache[state] = t
        self._measure_failed.add(state)
        logger.warning(
            "measurement failed (candidate degraded to analytic cost "
            "%.6gs): %s", t, why,
        )
        return t

    def _measure_state(self, state: State) -> float:
        if state in self._measure_cache:
            return self._measure_cache[state]
        try:
            t = self.measure_fn(self.mdp.plan(state))
        except Exception as e:  # noqa: BLE001 - degrade, never abort the run
            return self._degrade(state, repr(e))
        self._measure_cache[state] = t
        self.n_measurements += 1
        return t

    def _prefetch_measurements(self, states: List[State]) -> None:
        """Batch the round's candidate measurements through the fleet
        (one ``measure_many`` fan-out over the workers) so the
        re-ranking ``min()`` below only ever hits the local cache."""
        todo = [s for s in states if s not in self._measure_cache]
        if not todo or self.measure_backend is None:
            return
        plans = [self.mdp.plan(s) for s in todo]
        try:
            times = self.measure_backend.measure_plans(plans)
        except Exception as e:  # noqa: BLE001 - fall back to per-state path
            logger.warning("fleet prefetch failed (%r); measuring serially", e)
            return
        for st, t in zip(todo, times):
            if t is None:
                self._degrade(st, "fleet measurement failed")
            else:
                self._measure_cache[st] = t
                self.n_measurements += 1

    # ------------------------------------------------------------------
    def _round(self):
        if self.batch and all(isinstance(t, ArrayMCTS) for t in self.trees):
            # lockstep pending-leaf round: the K trees' concurrent
            # simulations price through ONE terminal_cost_batch call per
            # step — results identical to the per-tree loop (engine/batch)
            return run_decision_batch(self.trees, self.mdp,
                                      controller=self.controller)
        return [t.run_decision() for t in self.trees]

    def run(self, time_budget_s: Optional[float] = None) -> TuneResult:
        t0 = time.perf_counter()
        decisions = self.decisions  # non-empty on a checkpoint resume
        controller = self.controller
        interrupted: Optional[dict] = None
        while not self.trees[0].done:
            if time_budget_s and time.perf_counter() - t0 > time_budget_s:
                break
            if controller is not None:
                controller.begin_round()
            results = self._round()

            # winner: best complete schedule across trees; optionally
            # re-rank the (deduped) candidates by real measurement
            # (paper Fig. 6's commented line).
            if self.measure_fn is not None:
                ranked = sorted(
                    range(len(results)), key=lambda i: results[i].best_cost
                )
                seen: Dict[State, int] = {}
                for i in ranked:
                    st = results[i].best_state
                    if st is not None and st not in seen:
                        seen[st] = i
                self._prefetch_measurements(list(seen))
                best_i = min(
                    seen.values(),
                    key=lambda i: self._measure_state(results[i].best_state),
                )
            else:
                best_i = min(
                    range(len(results)), key=lambda i: results[i].best_cost
                )
            win = results[best_i]
            decisions.append(
                {
                    "depth": len(self.trees[0].root_state),
                    "stage": self.mdp.space.stages[len(self.trees[0].root_state)].name,
                    "action": win.action,
                    "winner_tree": best_i,
                    "winner_greedy": self.greedy_flags[best_i],
                    "best_cost": win.best_cost,
                }
            )
            for t in self.trees:
                t.advance_root(win.action)

            if controller is not None:
                # a cancel can truncate the round mid-iteration
                # (engine/batch.py); a truncated boundary is NOT
                # canonical, so it is neither counted, delayed, nor
                # checkpointed — the last cadence checkpoint (all full
                # rounds) stays the resume point
                truncated = controller.round_truncated
                if not truncated:
                    controller.round_done(self.snapshot)
                reason = controller.should_stop()
                if reason is not None and not self.trees[0].done:
                    ckpt = False
                    if not truncated:
                        # final boundary checkpoint (idempotent with a
                        # cadence checkpoint on the same round)
                        ckpt = controller.checkpoint(self.snapshot)
                    interrupted = {
                        "reason": reason,
                        "rounds_done": len(decisions),
                        "rounds_total": len(self.mdp.space.stages),
                        "round_truncated": truncated,
                        "checkpointed": bool(ckpt),
                    }
                    break

        # final schedule: the best complete state any tree ever saw
        best_tree = min(self.trees, key=lambda t: t.global_best)
        final_state = best_tree.global_best_state
        final_cost = best_tree.global_best
        measured = None
        if self.measure_fn is not None and final_state is not None:
            # winner by real time among all measured candidates + final
            cands = dict(self._measure_cache)
            cands[final_state] = self._measure_state(final_state)
            final_state = min(cands, key=cands.get)
            # a degraded candidate's entry is its analytic cost, not a
            # real measurement — never report it as one
            if final_state not in self._measure_failed:
                measured = cands[final_state]
            final_cost = self.mdp.terminal_cost(final_state)
        stats = {}
        if interrupted is not None:
            # best-so-far provenance: callers (the daemon, the plan store)
            # must treat this result as partial — never record it as THE
            # answer for the request
            stats["interrupted"] = interrupted
        return TuneResult(
            plan=self.mdp.plan(final_state),
            cost=final_cost,
            measured=measured,
            n_evals=getattr(self.mdp.cost_model, "n_evals", 0),
            n_measurements=self.n_measurements,
            wall_time_s=time.perf_counter() - t0,
            decisions=decisions,
            algo="mcts",
            engine=self.engine,
            cache_hits=self.cache.hits if self.cache else 0,
            cache_misses=self.cache.misses if self.cache else 0,
            stats=stats,
            n_measure_failures=self.n_measure_failures,
        )


@dataclass
class MCTSEnsembleBackend:
    """``SearchBackend`` adapter for the ProTuner ensemble (see
    ``repro.core.engine.backend``)."""

    algo: str = "mcts"
    config: MCTSConfig = field(default_factory=MCTSConfig)
    engine: str = "array"
    name: str = "mcts"

    def run(
        self,
        mdp,
        *,
        seed: int = 0,
        time_budget_s: Optional[float] = None,
        measure_fn: Optional[Callable] = None,
        measure_backend=None,
        n_standard: int = 15,
        n_greedy: int = 1,
        cache: Optional[bool] = None,
        batch: Optional[bool] = None,
        controller=None,
        resume: Optional[dict] = None,
        **_,
    ) -> TuneResult:
        mc = dataclasses.replace(self.config, seed=seed)
        # paper protocol: only the cost+real_* variants re-rank by real
        # measurement at root synchronization
        use_measure = measure_fn if "real" in self.algo else None
        use_backend = measure_backend if "real" in self.algo else None
        tuner = ProTuner(
            mdp,
            n_standard=n_standard,
            n_greedy=n_greedy,
            mcts_config=mc,
            measure_fn=use_measure,
            measure_backend=use_backend,
            seed=seed,
            engine=self.engine,
            cache=cache,
            batch=batch,
            controller=controller,
            resume=resume,
        )
        res = tuner.run(time_budget_s=time_budget_s)
        res.algo = self.algo
        return res
