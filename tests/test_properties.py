"""Hypothesis property tests on system invariants.

The whole module skips cleanly when ``hypothesis`` is not installed (it is
an optional dev dependency — CI installs it; minimal environments run the
rest of the tier-1 suite without it)."""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.configs import ARCH_IDS, get_config, get_shape
from repro.core.autotuner import NoisyCostModel, make_mdp
from repro.core.cost_model import AnalyticCostModel
from repro.core.engine import CachedMDP
from repro.core.mcts import MCTS, MCTSConfig
from repro.core.space import SINGLE_POD, MULTI_POD, SchedulePlan, ScheduleSpace
from repro.kernels import ref

SETTINGS = settings(max_examples=30, deadline=None)


@st.composite
def cell(draw):
    arch = draw(st.sampled_from(ARCH_IDS))
    shape = draw(st.sampled_from(["train_4k", "prefill_32k", "decode_32k"]))
    mesh = draw(st.sampled_from([SINGLE_POD, MULTI_POD]))
    return arch, shape, mesh


@SETTINGS
@given(cell(), st.integers(0, 2**31 - 1))
def test_random_plans_always_cost_finite_positive(c, seed):
    """MDP invariant: EVERY complete schedule has a finite positive cost
    (infeasible = penalized, never rejected)."""
    arch, shape_name, mesh = c
    space = ScheduleSpace(get_config(arch), get_shape(shape_name), mesh)
    cm = AnalyticCostModel(get_config(arch), get_shape(shape_name), mesh)
    plan = space.random_plan(random.Random(seed))
    cost = cm.cost(plan)
    assert np.isfinite(cost) and cost > 0


@SETTINGS
@given(cell(), st.integers(0, 2**31 - 1))
def test_action_sequences_roundtrip(c, seed):
    arch, shape_name, mesh = c
    space = ScheduleSpace(get_config(arch), get_shape(shape_name), mesh)
    actions = space.random_actions(random.Random(seed))
    plan = space.plan_from_actions(actions)
    # every stage's chosen value is one of its options
    for s, a in zip(space.stages, actions):
        assert getattr(plan, s.name) == s.options[a]
    assert SchedulePlan.from_dict(plan.to_dict()) == plan


@st.composite
def plan_batch(draw):
    """A (cfg, shape, mesh, space, plans) tuple with arbitrary plans —
    duplicates injected deliberately, since concurrent rollouts collide
    on schedules; the mesh is sampled too, so the columnar kernel's
    multi-pod branches (pod-scaled dp, pod-link bandwidth blending) get
    certified alongside the single-pod ones."""
    arch = draw(st.sampled_from(
        ["granite-3-2b", "granite-moe-1b-a400m", "falcon-mamba-7b"]
    ))  # dense attn / MoE / SSM — every kernel branch family
    shape_name = draw(st.sampled_from(["train_4k", "decode_32k"]))
    mesh = draw(st.sampled_from([SINGLE_POD, MULTI_POD]))
    cfg, shape = get_config(arch).reduced(), get_shape(shape_name)
    space = ScheduleSpace(cfg, shape, mesh)
    seeds = draw(st.lists(st.integers(0, 2**31 - 1), min_size=1, max_size=6))
    plans = [space.random_plan(random.Random(s)) for s in seeds]
    if draw(st.booleans()):
        plans = plans + plans[: draw(st.integers(1, len(plans)))]
    return cfg, shape, mesh, space, plans


@SETTINGS
@given(plan_batch())
def test_cost_batch_equals_scalar_sweep(batch):
    """The batch-pricing contract: ``cost_batch(plans)`` returns EXACTLY
    ``[cost(p) for p in plans]`` — element order preserved, duplicates
    included, floats compared with ``==`` (bit-identity, not tolerance).
    Held by the default (columnar, size-dispatched) model on random
    batches of both cell kinds."""
    cfg, shape, mesh, space, plans = batch
    cm = AnalyticCostModel(cfg, shape, mesh)
    scalar = [cm.cost(p) for p in plans]
    batched = cm.cost_batch(plans)
    assert batched == scalar
    # a second batched pass (warm context) returns the same values
    assert cm.cost_batch(plans) == scalar
    # unique plans are priced once per batch call
    n0 = cm.n_evals
    cm.cost_batch(plans)
    assert cm.n_evals - n0 == len(set(plans))


@SETTINGS
@given(plan_batch())
def test_columnar_kernel_equals_scalar_oracle(batch):
    """The columnar refactor's load-bearing property: the vectorized
    kernel (forced via ``columnar_min_batch=1`` so even batches of one run
    column math) and the pre-columnar scalar oracle (``columnar=False``)
    price every random batch bit-identically — ``cost``, ``cost_batch``
    (duplicates included), and every ``terms`` field down to the
    ``details`` dict."""
    cfg, shape, mesh, space, plans = batch
    kern = AnalyticCostModel(
        cfg, shape, mesh, columnar=True, columnar_min_batch=1
    )
    oracle = AnalyticCostModel(cfg, shape, mesh, columnar=False)
    want = [oracle.cost(p) for p in plans]
    assert kern.cost_batch(plans) == want
    assert [kern.cost(p) for p in plans] == want
    assert kern.terms(plans[0]).to_dict() == oracle.terms(plans[0]).to_dict()


@SETTINGS
@given(plan_batch())
def test_featurize_columns_matches_featurize_batch(batch):
    """The shared-encoding seam: featurizing a ``PlanColumns`` batch for
    the learned model produces the SAME float32 matrix as featurizing the
    plan objects, so one encode feeds the kernel and the MLP alike."""
    from repro.core.cost_model import PlanColumns
    from repro.core.learned_cost import featurize_batch, featurize_columns

    cfg, shape, mesh, space, plans = batch
    cols = PlanColumns.from_plans(plans)
    a = featurize_batch(plans, space)
    b = featurize_columns(cols, space)
    assert a.dtype == b.dtype and a.shape == b.shape
    assert (a == b).all()


@SETTINGS
@given(plan_batch(), st.floats(0.05, 0.5), st.integers(0, 10**6))
def test_noisy_cost_batch_equals_scalar_sweep(batch, sigma, seed):
    cfg, shape, mesh, space, plans = batch
    noisy = NoisyCostModel(AnalyticCostModel(cfg, shape, mesh), sigma, seed)
    assert noisy.cost_batch(plans) == [noisy.cost(p) for p in plans]


@st.composite
def state_batch(draw):
    """A (CachedMDP, states) pair; states are complete schedules with
    duplicates injected."""
    from repro.core.mdp import ScheduleMDP

    cfg, shape = get_config("granite-moe-1b-a400m").reduced(), get_shape("train_4k")
    space = ScheduleSpace(cfg, shape, SINGLE_POD)
    mdp = ScheduleMDP(space, AnalyticCostModel(cfg, shape, SINGLE_POD))
    seeds = draw(st.lists(st.integers(0, 2**31 - 1), min_size=1, max_size=6))
    states = [tuple(space.random_actions(random.Random(s))) for s in seeds]
    if draw(st.booleans()):
        states = states + states[: draw(st.integers(1, len(states)))]
    return CachedMDP(mdp), states


@SETTINGS
@given(state_batch())
def test_terminal_cost_batch_cache_consistency(batch):
    """``CachedMDP.terminal_cost_batch``: scalar-identical values, hit/miss
    accounting sums to the batch size, and a warm cache never changes the
    returned values (it only converts misses to hits)."""
    mdp, states = batch
    cache = mdp.cache
    cold = mdp.terminal_cost_batch(states)
    assert cache.hits + cache.misses == len(states)
    assert cache.misses == len(set(states))  # duplicates hit in-batch
    warm = mdp.terminal_cost_batch(states)
    assert warm == cold
    assert cache.misses == len(set(states))  # warm pass: all hits
    assert cache.hits + cache.misses == 2 * len(states)
    # scalar lookups agree element-for-element
    assert [mdp.terminal_cost(s) for s in states] == cold
    # the wrapped cost model priced each unique schedule exactly once
    assert mdp.cost_model.n_evals == len(set(states))


@SETTINGS
@given(state_batch(), st.integers(1, 12))
def test_partial_cost_batch_cache_consistency(batch, cut):
    """Mixed prefix/terminal batches through ``partial_cost_batch`` match
    the scalar method and keep ``hits + misses == len(batch)``."""
    mdp, states = batch
    prefixes = [s[: cut % (len(s) + 1)] for s in states]  # some terminal
    mixed = prefixes + states[:1]
    cold = mdp.partial_cost_batch(mixed)
    assert mdp.cache.hits + mdp.cache.misses == len(mixed)
    assert mdp.partial_cost_batch(mixed) == cold
    assert [mdp.partial_cost(s) for s in mixed] == cold


# ---------------------------------------------------------------------------
# Evolutionary operator closure (core/evolve.py)
#
# The operator catalog moves option *indices*, never raw values, so closure
# over ``ScheduleSpace`` should hold by construction — these properties pin
# it: every operator (and uniform crossover) applied to a valid plan yields
# a plan inside the space, and decoding the child plan re-encodes to exactly
# the child's action tuple.
# ---------------------------------------------------------------------------


@st.composite
def space_and_state(draw):
    """A (space, valid action tuple) pair over reduced configs of all three
    architecture families, both cell kinds, both meshes."""
    arch = draw(st.sampled_from(
        ["granite-3-2b", "granite-moe-1b-a400m", "falcon-mamba-7b"]
    ))
    shape_name = draw(st.sampled_from(["train_4k", "decode_32k"]))
    mesh = draw(st.sampled_from([SINGLE_POD, MULTI_POD]))
    space = ScheduleSpace(
        get_config(arch).reduced(), get_shape(shape_name), mesh
    )
    seed = draw(st.integers(0, 2**31 - 1))
    return space, tuple(space.random_actions(random.Random(seed)))


@SETTINGS
@given(space_and_state(), st.integers(0, 2**31 - 1))
def test_every_mutation_operator_is_closed(s, opseed):
    """Each single operator returns a DIFFERENT valid index for its stage,
    and the mutated plan decodes and re-encodes to itself."""
    from repro.core.evolve import encode_plan, mutation_operators

    space, actions = s
    rng = random.Random(opseed)
    ops = mutation_operators(space)
    # only single-option stages are excluded from the catalog
    assert {d for _n, d, _o in ops} == {
        d for d, st_ in enumerate(space.stages) if len(st_.options) >= 2
    }
    for name, depth, op in ops:
        new_idx = op(actions[depth], rng)
        assert 0 <= new_idx < len(space.stages[depth].options)
        assert new_idx != actions[depth]
        child = list(actions)
        child[depth] = new_idx
        plan = space.plan_from_actions(child)
        assert getattr(plan, space.stages[depth].name) == \
            space.stages[depth].options[new_idx]
        assert encode_plan(space, plan) == tuple(child)


@SETTINGS
@given(space_and_state(), st.integers(0, 2**31 - 1), st.floats(0.01, 1.0))
def test_mutate_is_closed_and_never_identity(s, opseed, rate):
    from repro.core.evolve import encode_plan, mutate, mutation_operators

    space, actions = s
    ops = mutation_operators(space)
    child = mutate(actions, random.Random(opseed), ops, rate)
    for stage, a in zip(space.stages, child):
        assert 0 <= a < len(stage.options)
    assert encode_plan(space, space.plan_from_actions(child)) == child
    if ops:  # mutate forces at least one operator when none fired
        assert child != tuple(actions)


@SETTINGS
@given(space_and_state(), st.integers(0, 2**31 - 1), st.integers(0, 2**31 - 1))
def test_crossover_is_closed(s, seed_b, seed_x):
    from repro.core.evolve import crossover, encode_plan

    space, a = s
    b = tuple(space.random_actions(random.Random(seed_b)))
    child = crossover(a, b, random.Random(seed_x))
    for x, ga, gb in zip(child, a, b):
        assert x in (ga, gb)
    assert encode_plan(space, space.plan_from_actions(child)) == child


# ---------------------------------------------------------------------------
# The jitted pricing kernel's tolerance contract (pricing="jit")
#
# ``_terms_jitted`` replays the same float64 arithmetic as the certified
# columnar kernel, but XLA is free to contract multiply-adds: empirically
# the two agree to 1-2 ULPs (max relative difference ~3.5e-16 across these
# grids on this build) — NOT bit-identical.  The pinned CONTRACT is
# relative agreement within ``JIT_RTOL``; because it is a tolerance, the
# jitted path carries the versioned ``JIT_PRICING_TAG`` so cache/store
# entries priced under different contracts never mix.
# ---------------------------------------------------------------------------

_JIT_PAIRS = {}


def _jit_pair(arch, shape_name, mesh):
    """Memoized (jit model, columnar model, space) per cell — the jit
    compile cache is per-model, so reusing models across hypothesis
    examples bounds the XLA compile count for the whole module."""
    key = (arch, shape_name, mesh.names)
    if key not in _JIT_PAIRS:
        cfg, shape = get_config(arch).reduced(), get_shape(shape_name)
        _JIT_PAIRS[key] = (
            AnalyticCostModel(cfg, shape, mesh, pricing="jit",
                              columnar_min_batch=1),
            AnalyticCostModel(cfg, shape, mesh),
            ScheduleSpace(cfg, shape, mesh),
        )
    return _JIT_PAIRS[key]


@SETTINGS
@given(
    st.sampled_from(["granite-3-2b", "granite-moe-1b-a400m",
                     "falcon-mamba-7b"]),
    st.sampled_from(["train_4k", "decode_32k"]),
    st.lists(st.integers(0, 2**31 - 1), min_size=8, max_size=8, unique=True),
)
def test_jitted_kernel_matches_columnar_within_rtol(arch, shape_name, seeds):
    """``pricing="jit"`` vs the exact columnar kernel on random plan
    batches: elementwise relative agreement within JIT_RTOL (see module
    note above for the exact-vs-ULP status), and the jit model carries a
    non-exact pricing tag while both exact paths share "exact"."""
    from repro.core.cost_model import JIT_PRICING_TAG, JIT_RTOL

    jit, col, space = _jit_pair(arch, shape_name, SINGLE_POD)
    plans = [space.random_plan(random.Random(s)) for s in seeds]
    a = np.asarray(jit.cost_batch(plans))
    b = np.asarray(col.cost_batch(plans))
    np.testing.assert_allclose(a, b, rtol=JIT_RTOL, atol=0.0)
    assert jit.pricing_tag == JIT_PRICING_TAG != "exact"
    assert col.pricing_tag == "exact"


def test_jitted_kernel_multipod_parity_fixed_batch():
    """Deterministic multi-pod leg (pod-scaled dp, pod-link blending) of
    the jit-vs-columnar contract — fixed batch so it costs exactly two
    extra XLA compiles."""
    from repro.core.cost_model import JIT_RTOL

    for shape_name in ("train_4k", "decode_32k"):
        jit, col, space = _jit_pair(
            "granite-moe-1b-a400m", shape_name, MULTI_POD
        )
        plans = [space.random_plan(random.Random(s)) for s in range(16)]
        np.testing.assert_allclose(
            np.asarray(jit.cost_batch(plans)),
            np.asarray(col.cost_batch(plans)),
            rtol=JIT_RTOL, atol=0.0,
        )


def test_jit_crossover_threshold_lowered_and_pinned():
    """Acceptance OR-branch: at batch 1 the jitted kernel does NOT beat the
    warm scalar replay (jax dispatch is ~100µs flat on CPU vs ~30µs for
    one scalar walk), so instead the measured jit-vs-scalar crossover —
    between 4 and 8 on the decode headline cell — is pinned here as
    JIT_MIN_BATCH, strictly below the columnar threshold (16).  Batches
    under the threshold price through the EXACT scalar replay."""
    from repro.core.cost_model import JIT_MIN_BATCH

    assert JIT_MIN_BATCH == 8 < 16
    cfg, shape = get_config("granite-3-2b").reduced(), get_shape("decode_32k")
    m = AnalyticCostModel(cfg, shape, SINGLE_POD, pricing="jit")
    assert m.columnar_min_batch == JIT_MIN_BATCH
    exact = AnalyticCostModel(cfg, shape, SINGLE_POD)
    assert exact.columnar_min_batch == 16


@SETTINGS
@given(st.integers(0, 10**6), st.floats(0.05, 0.5))
def test_noisy_cost_model_deterministic(seed, sigma):
    mdp = make_mdp("granite-3-2b", "train_4k")
    noisy = NoisyCostModel(mdp.cost_model, sigma=sigma, seed=seed)
    plan = mdp.space.plan_from_actions(mdp.space.default_actions())
    assert noisy.cost(plan) == noisy.cost(plan)
    assert noisy.cost(plan) > 0


@SETTINGS
@given(
    st.integers(1, 64),
    st.integers(16, 200),
    st.floats(0.1, 100.0),
)
def test_quantize_error_bound(rows, cols, scale):
    key = jax.random.PRNGKey(rows * 1000 + cols)
    x = jax.random.normal(key, (rows, cols)) * scale
    q, s = ref.quantize_int8(x)
    xd = ref.dequantize_int8(q, s)
    err = np.abs(np.asarray(xd - x))
    bound = np.asarray(s) * 0.5 + 1e-6
    assert (err <= bound).all()
    assert np.abs(np.asarray(q)).max() <= 127


@SETTINGS
@given(st.integers(2, 6), st.integers(1, 6), st.integers(0, 1000))
def test_mcts_never_produces_invalid_state(depth_actions, iters, seed):
    """Tree ops keep states inside the MDP for arbitrary budgets."""
    mdp = make_mdp("granite-moe-1b-a400m", "train_4k")
    t = MCTS(mdp, MCTSConfig(iters_per_decision=iters, seed=seed))
    res = t.run_decision()
    assert 0 <= res.action < mdp.n_actions(())
    assert mdp.is_terminal(res.best_state)
    assert len(res.best_state) == mdp.space.n_stages


@SETTINGS
@given(st.integers(0, 10**6))
def test_rendezvous_rebalance_is_stable(seed):
    """Adding a host only moves shards TO the new host (rendezvous)."""
    from repro.runtime.fault_tolerance import rebalance

    rng = random.Random(seed)
    n = rng.randint(2, 12)
    hosts = [f"h{i}" for i in range(n)]
    before = rebalance(hosts, 48)
    after = rebalance(hosts + ["hNEW"], 48)
    for s in range(48):
        if before[s] != after[s]:
            assert after[s] == "hNEW"


@SETTINGS
@given(st.integers(0, 2**31 - 1), st.integers(1, 16))
def test_pipeline_index_math_disjoint(seed, hosts):
    """For any host count dividing the batch, shards partition the batch."""
    from repro.configs.base import InputShape
    from repro.data.pipeline import DataConfig, Pipeline

    cfg = get_config("granite-3-2b").reduced()
    batch = 16
    if batch % hosts != 0:
        hosts = 1
    shape = InputShape("t", 8, batch, "train")
    full = Pipeline(cfg, shape, DataConfig(seed=seed)).batch_at(2)["inputs"]
    parts = [
        Pipeline(cfg, shape, DataConfig(seed=seed, host_count=hosts, host_index=h)).batch_at(2)["inputs"]
        for h in range(hosts)
    ]
    np.testing.assert_array_equal(np.concatenate(parts), full)


@SETTINGS
@given(st.sampled_from(ARCH_IDS))
def test_sharding_specs_are_mesh_consistent(arch):
    """Every generated PartitionSpec references only mesh axes and divides
    the dims it shards."""
    from repro.sharding.rules import ShardingRules, _axes_size

    cfg = get_config(arch).reduced()
    shape = get_shape("train_4k")
    space = ScheduleSpace(cfg, shape, SINGLE_POD)
    plan = space.plan_from_actions(space.default_actions())
    rules = ShardingRules(cfg, shape, plan, SINGLE_POD)
    from repro.models import transformer

    params = jax.eval_shape(lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    specs = rules.param_pspecs(params)

    def check(leaf, spec):
        for dim, entry in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                assert a in SINGLE_POD.names
            assert dim % _axes_size(SINGLE_POD, axes) == 0

    jax.tree.map(check, params, specs,
                 is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))
