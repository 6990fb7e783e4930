"""Top-level ProTuner API: ``autotune(arch, shape, algo, ...)``.

Algorithms (paper §5 protocol, plus the complete-plan portfolio):
  mcts_*    — ProTuner ensemble (15 standard + 1 greedy MCTS), Table-1 variants
  beam      — beam search, size 32, 5 passes (Adams et al. baseline)
  greedy    — beam size 1
  random    — random search (no cost model)
  evolve    — evolutionary search over complete plans (core/evolve.py);
              with a plan_store, seeded from the cell's recorded plans
  portfolio — race evolve/mcts/beam/random on one shared transposition
              cache and eval budget (core/evolve.py)

Every search runs in the calling process on the exact analytic cost
(``core/cost_model.py``); the MCTS ensemble's default engine is the
flat-array ``ArrayMCTS`` with lockstep batched rounds and a shared
transposition cache.  A ``measure_fn`` or ``measure_backend`` adds real
measurement at every root synchronization — the ``mcts_cost+real_*``
configurations.
"""
from __future__ import annotations

import hashlib
import math
from typing import Callable, Optional

from repro.configs import get_config, get_shape
from repro.core.cost_model import AnalyticCostModel
from repro.core.engine import ENGINES
from repro.core.engine.backend import TABLE1, SearchBackend, resolve_backend
from repro.core.ensemble import TuneResult
from repro.core.mdp import ScheduleMDP
from repro.core.space import MULTI_POD, SINGLE_POD, ScheduleSpace


class NoisyCostModel:
    """Deterministic multiplicative log-normal noise on top of the analytic
    model — simulates a learned cost model's error (paper §3); per-plan noise
    is a pure hash so search remains reproducible."""

    def __init__(self, inner: AnalyticCostModel, sigma: float = 0.0, seed: int = 0):
        self.inner = inner
        self.sigma = sigma
        self.seed = seed

    @property
    def n_evals(self):
        return self.inner.n_evals

    def _noise(self, plan) -> float:
        if not self.sigma:
            return 1.0
        # Box-Muller from two INDEPENDENT uniforms: disjoint halves of a
        # 16-byte digest (a single 8-byte digest reused for both radius and
        # angle correlates them and skews the distribution off log-normal)
        h = hashlib.blake2b(
            (str(self.seed) + repr(plan)).encode(), digest_size=16
        ).digest()
        u1 = int.from_bytes(h[:8], "big") / 2**64
        u2 = int.from_bytes(h[8:16], "big") / 2**64
        z = math.sqrt(-2.0 * math.log(max(u1, 1e-12))) * math.cos(2 * math.pi * u2)
        return math.exp(self.sigma * z)

    def cost(self, plan) -> float:
        return self.inner.cost(plan) * self._noise(plan)

    def cost_batch(self, plans) -> list:
        """Batched pricing: inner costs amortize through the analytic
        model's batch path, then the same deterministic per-plan noise is
        applied — ``cost_batch(plans) == [cost(p) for p in plans]``."""
        base = self.inner.cost_batch(plans)
        return [b * self._noise(p) for b, p in zip(base, plans)]

    def partial_cost(self, actions, space) -> float:
        defaults = space.default_actions()
        full = list(actions) + defaults[len(actions):]
        return self.cost(space.plan_from_actions(full))

    def terms(self, plan):
        return self.inner.terms(plan)


def make_mdp(
    arch: str,
    shape_name: str,
    mesh: str = "single",
    noise_sigma: float = 0.0,
    noise_seed: int = 0,
    pricing: Optional[str] = None,
) -> ScheduleMDP:
    """Build one cell's MDP.  ``pricing`` selects the analytic kernel:
    None/"columnar" (exact, default), "scalar" (the exact oracle replay),
    or "jit" (the jax-jitted kernel — JIT_RTOL tolerance contract and a
    versioned pricing tag; see cost_model.py)."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mspec = MULTI_POD if mesh == "multi" else SINGLE_POD
    space = ScheduleSpace(cfg, shape, mspec)
    cm = AnalyticCostModel(cfg, shape, mspec, pricing=pricing)
    if noise_sigma:
        cm = NoisyCostModel(cm, noise_sigma, noise_seed)
    return ScheduleMDP(space, cm)


# TABLE1 lives in repro.core.engine.backend (imported above) — re-exported
# here for backward compatibility with existing callers/tests.


def autotune(
    arch: str,
    shape_name: str,
    *,
    algo: str = "mcts_30s",
    mesh: str = "single",
    seed: int = 0,
    n_standard: int = 15,
    n_greedy: int = 1,
    measure_fn: Optional[Callable] = None,
    measure_backend=None,
    time_budget_s: Optional[float] = None,
    noise_sigma: float = 0.0,
    mdp: Optional[ScheduleMDP] = None,
    engine: str = "array",
    cache: Optional[bool] = None,
    batch: Optional[bool] = None,
    plan_store=None,
    pricing: Optional[str] = None,
    controller=None,
    resume: Optional[dict] = None,
) -> TuneResult:
    """Tune one (arch × shape × mesh) cell.

    ``engine`` selects the MCTS tree representation — the default is the
    vectorized ``"array"`` engine with batched leaf evaluation and the
    shared transposition cache, certified bit-identical to the paper-
    faithful ``"reference"`` engine by ``tests/test_differential.py``;
    ``cache`` forces the shared transposition cache on/off (default: on
    for the array engine); ``batch`` forces lockstep batched leaf
    evaluation on/off (default: on for the array engine).  All algorithms
    dispatch through the ``SearchBackend`` protocol
    (``repro.core.engine.backend``).

    ``measure_backend`` threads a fleet-bound measurement adapter
    (``MeasurementFleet.bind(...)``, see ``repro.core.measure_fleet``)
    through to the ensemble: ``mcts_cost+real_*`` runs then batch each
    root synchronization's candidate measurements through the fleet's
    workers instead of blocking the search loop on serial subprocess
    compiles, and a failed measurement degrades that candidate to its
    exact analytic cost (counted on ``TuneResult.n_measure_failures``)
    instead of aborting the run.

    ``controller`` mounts a round-boundary ``RunController``
    (``repro.core.run_control``): a deadline or cancel finishes the
    current decision round and returns best-so-far with
    ``TuneResult.stats["interrupted"]`` provenance; ``resume`` restores a
    ``ProTuner.snapshot()`` checkpoint so the run replays the remaining
    rounds bit-identically.  An uninterrupted run with a controller
    mounted is bit-identical to one without.  An interrupted (partial)
    result is never recorded into ``plan_store``."""
    assert engine in ENGINES, engine
    store_req = None
    if plan_store is not None:
        # persistent PlanStore (repro.service.store): answer a repeat
        # request from disk (from_store=True, zero evals), record a cold
        # result after the run.  The store key covers the value-affecting
        # settings of THIS signature — a caller passing a custom ``mdp``
        # must guarantee it matches them (the daemon does; see
        # service/daemon.py for cell-cache warm start on top of this).
        from repro.service.store import canonical_request

        store_req = canonical_request(
            arch, shape_name, mesh=mesh, algo=algo, seed=seed,
            time_budget_s=time_budget_s, n_standard=n_standard,
            n_greedy=n_greedy, noise_sigma=noise_sigma, pricing=pricing,
        )
        hit = plan_store.lookup(store_req)
        if hit is not None:
            return hit
    seed_plans = None
    if plan_store is not None and algo in ("evolve", "portfolio"):
        # warm-start the evolutionary population from the store's recorded
        # plans for this cell (any algo/seed — a good plan is a good seed);
        # non-evolutionary backends ignore seed_plans
        seed_plans = plan_store.seed_plans(
            arch=arch, shape=shape_name, mesh=mesh
        )
    mdp = mdp or make_mdp(arch, shape_name, mesh, noise_sigma, seed,
                          pricing=pricing)
    backend: SearchBackend = resolve_backend(algo, engine=engine)
    res = backend.run(
        mdp,
        seed=seed,
        time_budget_s=time_budget_s,
        measure_fn=measure_fn,
        measure_backend=measure_backend,
        n_standard=n_standard,
        n_greedy=n_greedy,
        cache=cache,
        batch=batch,
        seed_plans=seed_plans,
        controller=controller,
        resume=resume,
    )
    if plan_store is not None:
        plan_store.record(store_req, res)
    return res
