"""Learned cost model (paper §3): a small MLP trained on COMPLETE
schedules, in pure JAX.

Its role here is the Fig. 1/2 reproduction: a model trained on complete
schedules ranks complete schedules well but mis-ranks partial ones (their
default-completion features are off-distribution), which is what poisons
beam search at every depth — see ``benchmarks/fig12_partial_cost.py``.

The forward pass is jitted ONCE at module level (``_mlp_apply_jit``) and
reused by both the scalar and batched entry points; batches are padded to
the next power of two so the number of distinct compiled shapes is
logarithmic in the largest batch ever seen, not linear in the number of
distinct batch sizes.
"""
from __future__ import annotations

import random as _random
from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost_model import AnalyticCostModel, PlanColumns
from repro.core.space import SchedulePlan, ScheduleSpace


def featurize(plan: SchedulePlan, space: ScheduleSpace) -> np.ndarray:
    """One-hot per stage + numeric knobs (log-scaled).

    Width = sum(len(stage.options) for the cell's stages) + 4 log-scaled
    knobs + the overlap scalar; exactly one 1.0 inside each stage's one-hot
    block (tested in ``tests/test_learned_cost.py``)."""
    feats: List[float] = []
    for stage in space.stages:
        val = getattr(plan, stage.name)
        for opt in stage.options:
            feats.append(1.0 if opt == val else 0.0)
    feats.append(np.log2(plan.microbatches))
    feats.append(np.log2(plan.attn_block[0]))
    feats.append(np.log2(plan.attn_block[1]))
    feats.append(np.log2(plan.scan_chunk))
    feats.append(plan.overlap)
    return np.asarray(feats, np.float32)


def featurize_batch(
    plans: Sequence[SchedulePlan], space: ScheduleSpace
) -> np.ndarray:
    """``stack([featurize(p) for p in plans])`` as one (N, d) f32 matrix."""
    return np.stack([featurize(p, space) for p in plans])


def featurize_columns(cols: PlanColumns, space: ScheduleSpace) -> np.ndarray:
    """``featurize_batch`` from a ``PlanColumns`` encoding — element-for-
    element equal to featurizing the plan objects (tested), built entirely
    from the same structure-of-arrays the analytic columnar kernel prices,
    so one encode feeds both without a per-plan re-walk."""
    blocks: List[np.ndarray] = []
    for stage in space.stages:
        for onehot in cols.stage_onehots(stage):
            blocks.append(onehot.astype(np.float32))
    blocks.append(np.log2(cols.microbatches).astype(np.float32))
    blocks.append(np.log2(cols.bq).astype(np.float32))
    blocks.append(np.log2(cols.bkv).astype(np.float32))
    blocks.append(np.log2(cols.scan_chunk).astype(np.float32))
    blocks.append(cols.overlap.astype(np.float32))
    return np.stack(blocks, axis=1)


def _pad_len(n: int) -> int:
    """Next power of two ≥ n: bounds the jit compile-cache growth."""
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


@dataclass
class LearnedCostModel:
    params: dict
    space: ScheduleSpace
    mean: float
    std: float
    n_evals: int = 0
    n_forward: int = 0  # jitted MLP invocations; a whole batch counts ONCE

    def cost(self, plan: SchedulePlan) -> float:
        return self.cost_batch([plan])[0]

    def cost_batch(self, plans: Sequence[SchedulePlan]) -> List[float]:
        """Price the whole batch in ONE jitted forward pass.

        Contract: ``cost_batch(plans) ≈ [cost(p) for p in plans]`` to
        float32 round-off (XLA may fuse the padded matmul differently per
        batch shape, so this seam — unlike the analytic ``cost_batch`` — is
        an approximate-parity contract, not a bit-exact one)."""
        if len(plans) == 0:
            return []
        return self._predict(featurize_batch(plans, self.space))

    def _predict(self, X: np.ndarray) -> List[float]:
        """One jitted forward pass over a feature matrix, padded to the
        next power of two so compiled shapes stay logarithmic."""
        n = X.shape[0]
        pad = _pad_len(n)
        if pad > n:
            X = np.concatenate(
                [X, np.zeros((pad - n, X.shape[1]), np.float32)]
            )
        y = np.asarray(_mlp_apply_jit(self.params, X))[:n, 0]
        self.n_evals += n
        self.n_forward += 1
        out = np.exp(y.astype(np.float64) * self.std + self.mean)
        return [float(v) for v in out]

    def partial_cost(self, actions, space) -> float:
        defaults = space.default_actions()
        full = list(actions) + defaults[len(actions):]
        return self.cost(space.plan_from_actions(full))


def _mlp_init(key, d_in: int, hidden: int = 64) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s = lambda k, a, b: jax.random.normal(k, (a, b)) * (2.0 / a) ** 0.5
    return {
        "w1": s(k1, d_in, hidden), "b1": jnp.zeros(hidden),
        "w2": s(k2, hidden, hidden), "b2": jnp.zeros(hidden),
        "w3": s(k3, hidden, 1), "b3": jnp.zeros(1),
    }


def _mlp_apply(p: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.relu(x @ p["w1"] + p["b1"])
    h = jax.nn.relu(h @ p["w2"] + p["b2"])
    return h @ p["w3"] + p["b3"]


# jitted once, reused by every model instance; recompiles only per input
# SHAPE (batches are padded to powers of two by cost_batch)
_mlp_apply_jit = jax.jit(_mlp_apply)


@partial(jax.jit, static_argnames=("steps",))
def _fit_params(params, X, Y, W, steps: int, lr):
    """``steps`` of full-batch weighted-MSE gradient descent (one compiled
    scan; ``W`` masks padding rows so datasets can pad to power-of-two
    sizes without corrupting the loss)."""

    def step(p, _):
        def loss_fn(p):
            err = (_mlp_apply(p, X) - Y) ** 2
            return jnp.sum(err[:, 0] * W) / jnp.sum(W)

        loss, g = jax.value_and_grad(loss_fn)(p)
        p = jax.tree.map(lambda a, gg: a - lr * gg, p, g)
        return p, loss

    params, _ = jax.lax.scan(step, params, jnp.arange(steps))
    return params


def fit_learned_cost(
    space: ScheduleSpace,
    plans: Sequence[SchedulePlan],
    costs: Sequence[float],
    *,
    params: Optional[dict] = None,
    steps: int = 200,
    lr: float = 3e-3,
    seed: int = 0,
) -> LearnedCostModel:
    """Fit (or warm-start refit, via ``params``) the MLP on explicit
    ``(plan, cost)`` pairs.  Normalization (log-cost mean/std) is recomputed
    from THIS dataset, so a warm-started refit follows a shifted cost
    distribution."""
    X = featurize_batch(plans, space)
    logy = np.log(np.maximum(np.asarray(costs, np.float32), 1e-9))
    mean, std = float(logy.mean()), float(logy.std() + 1e-6)
    Y = ((logy - mean) / std).astype(np.float32)
    n = X.shape[0]
    pad = _pad_len(n)
    W = np.zeros(pad, np.float32)
    W[:n] = 1.0
    if pad > n:
        X = np.concatenate([X, np.zeros((pad - n, X.shape[1]), np.float32)])
        Y = np.concatenate([Y, np.zeros(pad - n, np.float32)])
    if params is None:
        params = _mlp_init(jax.random.PRNGKey(seed), X.shape[1])
    params = _fit_params(params, X, Y[:, None], W, steps, lr)
    return LearnedCostModel(params=params, space=space, mean=mean, std=std)


def train_learned_cost(
    space: ScheduleSpace,
    oracle: AnalyticCostModel,
    *,
    n_samples: int = 512,
    steps: int = 400,
    lr: float = 3e-3,
    seed: int = 0,
) -> LearnedCostModel:
    """Train on random complete schedules against the oracle's cost
    (the paper trains against measured runtimes of random programs).
    Labels price through ``cost_batch`` — one columnar-kernel pass for
    the whole training set, values identical to a scalar sweep."""
    rng = _random.Random(seed)
    plans = [space.random_plan(rng) for _ in range(n_samples)]
    y = oracle.cost_batch(plans)
    return fit_learned_cost(space, plans, y, steps=steps, lr=lr, seed=seed)


def ranking_correlation(
    model, oracle: AnalyticCostModel, space: ScheduleSpace, *,
    n: int = 128, seed: int = 1, partial_depth: Optional[int] = None,
) -> float:
    """Spearman rank correlation model-vs-oracle on complete schedules, or on
    partial prefixes (default-completed) when ``partial_depth`` is given.

    Both legs price through the batch seam (``cost_batch`` — one MLP
    forward pass / one columnar kernel pass for all ``n`` samples), the
    same path the fig-12 artifact exercises; models
    without a batch entry point fall back to a scalar sweep."""
    rng = _random.Random(seed)
    pred_plans, gold_plans = [], []
    for _ in range(n):
        actions = space.random_actions(rng)
        if partial_depth is not None:
            prefix = actions[:partial_depth]
            defaults = space.default_actions()
            full_actions = prefix + defaults[len(prefix):]
            # the model scores its (misleading) default completion; the
            # oracle scores the TRUE eventual schedule (the random one)
            pred_plans.append(space.plan_from_actions(full_actions))
            gold_plans.append(space.plan_from_actions(actions))
        else:
            plan = space.plan_from_actions(actions)
            pred_plans.append(plan)
            gold_plans.append(plan)

    def price(m, plans):
        batch = getattr(m, "cost_batch", None)
        if batch is not None:
            return batch(plans)
        return [m.cost(p) for p in plans]

    preds = price(model, pred_plans)
    golds = price(oracle, gold_plans)
    return _spearman(np.asarray(preds), np.asarray(golds))


def _spearman(a: np.ndarray, b: np.ndarray) -> float:
    ra = np.argsort(np.argsort(a)).astype(np.float64)
    rb = np.argsort(np.argsort(b)).astype(np.float64)
    ra -= ra.mean()
    rb -= rb.mean()
    denom = np.sqrt((ra**2).sum() * (rb**2).sum())
    return float((ra * rb).sum() / denom) if denom else 0.0
