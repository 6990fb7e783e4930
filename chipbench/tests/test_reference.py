"""The plain reference against the program at tiny widths in float32: the
same equations give the same logits, the MoE capacity drops included."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.reference import granite as ref
from chipbench.tests import tiny


def _program_logits(model, params, tokens):
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.core.space import SchedulePlan
    from repro.training.train_step import make_positions, make_prefill_step

    prog = tiny.config(model)["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog["overrides"])
    B, S = tokens.shape
    step = make_prefill_step(cfg, InputShape("p", S, B, "prefill"), SchedulePlan())
    return step(params, {"inputs": tokens, "positions": make_positions(cfg, B, S)})


def _zipf(n, V, seed=0):
    """Zipf ids after a run of 64 copies of one id, so that routing is
    uneven enough to pass an expert's capacity."""
    r = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, V + 1)
    ids = r.choice(V, size=(1, n), p=w / w.sum())
    ids[0, :64] = 7
    return jnp.asarray(ids, jnp.int32)


@pytest.mark.parametrize("model", [tiny.DENSE, tiny.MOE], ids=["dense", "moe"])
def test_logits_match_program(model):
    params = weights.make(model, weights.seed_key(3, 0))
    tokens = _zipf(96, model["vocab_size"])
    got = _program_logits(model, params, tokens)
    want = ref.seq_logits(params, tokens, model)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))
    assert ref.logits_rel_err(params, tokens, got, model) < 1e-5


def test_moe_drops_tokens_past_capacity(monkeypatch):
    """Zipf ids route unevenly: at 96 tokens some expert passes its capacity
    of 64, so the logits (which match the program's, above) differ from the
    logits of a router that drops nothing."""
    model = tiny.MOE
    params = weights.make(model, weights.seed_key(3, 0))
    tokens = _zipf(96, model["vocab_size"])
    assert ref.capacity(96, model) == 64  # 96*2*1.25/4 = 60, up to a multiple of 8
    capped = ref.seq_logits(params, tokens, model)
    monkeypatch.setattr(ref, "capacity", lambda T, m: T * m["num_experts_per_tok"])
    jax.clear_caches()
    uncapped = ref.seq_logits(params, tokens, model)
    assert float(jnp.max(jnp.abs(capped - uncapped))) > 1e-3


def test_rope_rotates_pairs_of_halves():
    x = jnp.arange(8.0).reshape(1, 1, 1, 8)
    out = ref.rope(x, jnp.zeros((1, 1), jnp.int32), 10000.0)
    assert jnp.allclose(out, x)  # position 0 is the identity
    out = ref.rope(x, jnp.ones((1, 1), jnp.int32), 1.0)  # every angle is 1 rad
    c, s = np.cos(1.0), np.sin(1.0)
    want = np.concatenate([np.arange(4) * c - np.arange(4, 8) * s,
                           np.arange(4, 8) * c + np.arange(4) * s])
    assert np.allclose(np.asarray(out).ravel(), want, atol=1e-5)
