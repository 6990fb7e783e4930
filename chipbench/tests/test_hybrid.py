"""The Jamba hybrid against its plain reference at tiny widths in float32:
both periods of 14 layers, attention at slot 7 and the dt/B/C norms kept.
The prefill path, and serving's feed and decode through the cache, give the
reference's logits; attention moved to slot 0, or the norms left out, does
not."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights, weights_jamba
from chipbench.reference import jamba as ref

HYBRID = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 28,
          "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 16,
          "vocab_size": 256, "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
          "hidden_act": "silu", "dtype": "float32", "attn_layer_offset": 7,
          "attn_layer_period": 14, "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_expand": 2,
          "mamba_dt_rank": 8, "mamba_conv_bias": True, "mamba_proj_bias": False,
          "num_experts": 1}
OVERRIDES = {"d_model": 64, "n_heads": 4, "n_kv_heads": 1, "head_dim": 16, "d_ff": 128,
             "vocab_size": 256, "ssm_state": 8, "d_inner": 128, "dt_rank": 8,
             "dtype": "float32"}
# float32 on both sides: only the order of sums differs (PERF.md: 2.3e-7 read)
TOL = 1e-5


def _cfg(**over):
    from repro.configs import get_config

    return dataclasses.replace(get_config("jamba2-3b"), **OVERRIDES, **over)


def _tokens(S, seed=0):
    r = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, 257)
    return jnp.asarray(r.choice(256, size=(1, S), p=w / w.sum()), jnp.int32)


def _prefill(cfg, params, tokens):
    from repro.configs.base import InputShape
    from repro.core.space import SchedulePlan
    from repro.training.train_step import make_positions, make_prefill_step

    B, S = tokens.shape
    step = make_prefill_step(cfg, InputShape("p", S, B, "prefill"), SchedulePlan())
    return jax.jit(step)(params, {"inputs": tokens, "positions": make_positions(cfg, B, S)})


@pytest.fixture(scope="module")
def params():
    return weights_jamba.make(HYBRID, weights.seed_key(3, 0))


def test_weights_have_the_program_layout(params):
    from repro.models import transformer

    want = jax.eval_shape(lambda k: transformer.init_params(_cfg(), k), jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert all((a.shape, a.dtype) == (b.shape, b.dtype)
               for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)))
    assert "attn" in params["blocks"]["b7"] and "mamba" in params["blocks"]["b0"]


def test_prefill_matches_reference(params):
    tokens = _tokens(64)
    got = _prefill(_cfg(), params, tokens)
    want = ref.seq_logits(params, tokens, HYBRID)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))
    assert ref.logits_rel_err(params, tokens, got, HYBRID) < TOL


def _attention_first(params):
    """The same weights laid out for a program with attention at slot 0."""
    b = params["blocks"]
    order = [7] + [i for i in range(14) if i != 7]
    return dict(params, blocks={f"b{j}": b[f"b{i}"] for j, i in enumerate(order)})


def _without_norms(params):
    """The same weights without the dt/B/C norms' leaves."""
    drop = ("dt_norm", "b_norm", "c_norm")
    blocks = {n: dict(b, mamba={k: v for k, v in b["mamba"].items() if k not in drop})
              if "mamba" in b else b for n, b in params["blocks"].items()}
    return dict(params, blocks=blocks)


@pytest.mark.parametrize("fault", ["attention_at_slot_0", "no_ssm_norms"])
def test_planted_fault_breaks_the_comparison(params, fault):
    """The program with its attention layer at slot 0 (the weights moved
    with it), or without the dt/B/C norms, reads a thousand times the
    tolerance against the reference; the reference computed the same wrong
    way agrees with it."""
    tokens = _tokens(64)
    if fault == "attention_at_slot_0":
        cfg, p, kw = _cfg(attn_offset=0), _attention_first(params), {"attn_first": True}
    else:
        cfg, p, kw = _cfg(ssm_input_norms=False), _without_norms(params), {"norms": False}
    got = _prefill(cfg, p, tokens)
    assert ref.logits_rel_err(params, tokens, got, HYBRID) > 1000 * TOL
    assert ref.variant_rel_err(params, tokens, HYBRID, **kw) > 1000 * TOL
    with jax.default_matmul_precision("highest"):
        wrong = ref.mm("bsd,vd->bsv", ref.hidden(params, tokens, HYBRID, **kw), params["embed"])
    assert float(jnp.max(jnp.abs(got - wrong))) < 1e-4 * float(jnp.max(jnp.abs(wrong)))


def test_feed_and_decode_through_the_cache_match_reference(params, monkeypatch):
    """Two requests of different lengths share the engine's slots: every
    logit row the engine's step computed for a committed slot, prompt feeds
    and decodes alike, is the reference's full forward at that position."""
    from repro.serving import engine as eng_mod

    seen = []
    make = eng_mod.make_serve_step

    def spy(*a, **k):
        step = make(*a, **k)

        def recorded(params, cache, tokens, cur, commit=None):
            logits, cache = step(params, cache, tokens, cur, commit=commit)
            jax.debug.callback(lambda *x: seen.append([np.asarray(v) for v in x]),
                               logits, tokens, cur, commit)
            return logits, cache

        return recorded

    monkeypatch.setattr(eng_mod, "make_serve_step", spy)
    eng = eng_mod.ServingEngine(_cfg(), params, batch_slots=2, max_len=32)
    prompts = [np.asarray(_tokens(n, seed=n)[0]) for n in (9, 5)]
    uids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    done = {r.uid: r for r in eng.run()}
    assert sorted(done) == uids
    checked = 0
    for uid, prompt in zip(uids, prompts):
        seq = np.concatenate([prompt, np.asarray(done[uid].generated[:-1], np.int32)])
        want = np.asarray(ref.seq_logits(params, jnp.asarray(seq[None]), HYBRID)[0])
        slot = uids.index(uid)  # both admitted at once, in order
        for logits, tokens, cur, commit in seen:
            if commit[slot] and cur[slot] < len(seq) and tokens[slot] == seq[cur[slot]]:
                row = want[cur[slot]]
                err = np.linalg.norm(logits[slot] - row) / np.linalg.norm(row)
                assert err < TOL, (uid, int(cur[slot]), err)
                checked += 1
    assert checked == len(prompts[0]) + len(prompts[1]) + 2 * 4
