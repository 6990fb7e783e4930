"""Continuous-batching correctness: batched decode must equal solo decode.

Regression tests for the shared-`cur` / full-batch-prefill cache corruption
(slots at different lengths clobbered each other's KV / SSM state) and for
``run()`` result semantics.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer
from repro.serving.engine import ServingEngine


def _engine(arch: str, slots: int, *, max_len: int = 32, seed: int = 0):
    cfg = get_config(arch).reduced()
    params = transformer.init_params(cfg, jax.random.PRNGKey(seed))
    return cfg, params, ServingEngine(
        cfg, params, batch_slots=slots, max_len=max_len
    )


def _solo(cfg, params, prompt, max_new, *, max_len: int = 32):
    eng = ServingEngine(cfg, params, batch_slots=1, max_len=max_len)
    eng.submit(np.asarray(prompt, np.int32), max_new_tokens=max_new)
    (done,) = eng.run()
    return done.generated


# mixed lengths force the old shared-cur bug; 3 requests on 2 slots force a
# prefill (request 3) while a neighbour slot is mid-decode — the old
# full-batch `_single_feed` corrupted the neighbour's cache there
@pytest.mark.parametrize("arch", ["granite-3-2b", "falcon-mamba-7b"])
def test_batched_decode_matches_solo(arch):
    cfg, params, eng = _engine(arch, slots=2)
    prompts = [
        np.array([3, 1, 4, 1, 5, 9, 2], np.int32),
        np.array([2, 7], np.int32),
        np.array([6, 6, 6, 6], np.int32),
    ]
    uids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    done = eng.run()
    assert sorted(r.uid for r in done) == uids
    by_uid = {r.uid: r.generated for r in done}
    for uid, prompt in zip(uids, prompts):
        assert by_uid[uid] == _solo(cfg, params, prompt, 5), (
            f"{arch}: batched decode diverged from solo for uid {uid}"
        )


@pytest.mark.parametrize("arch", ["granite-3-2b", "falcon-mamba-7b"])
def test_slot_reuse_does_not_leak_state(arch):
    # second occupant of a slot must match a fresh engine (mamba conv/SSM
    # state is not position-masked, so the slot must be reset on assignment);
    # the engine donates its cache to every call, so this also checks that
    # a cache updated in place, reset and reused serves what a fresh one does
    cfg, params, eng = _engine(arch, slots=1)
    eng.submit(np.array([9, 8, 7], np.int32), max_new_tokens=4)
    eng.run()
    eng.submit(np.array([1, 2], np.int32), max_new_tokens=4)
    (second,) = eng.run()
    assert second.generated == _solo(cfg, params, [1, 2], 4)


def test_run_returns_only_this_calls_completions():
    _, _, eng = _engine("granite-3-2b", slots=2)
    eng.submit(np.array([1, 2], np.int32), max_new_tokens=2)
    first = eng.run()
    assert [r.uid for r in first] == [1]
    eng.submit(np.array([3], np.int32), max_new_tokens=2)
    second = eng.run()
    assert [r.uid for r in second] == [2]  # not [1, 2]
    assert [r.uid for r in eng.finished] == [1, 2]


def test_run_surfaces_still_active_requests():
    _, _, eng = _engine("granite-3-2b", slots=1)
    eng.submit(np.array([5], np.int32), max_new_tokens=8)
    eng.submit(np.array([6], np.int32), max_new_tokens=8)
    done = eng.run(max_steps=3)
    assert done == []
    assert eng.pending() == {"active": 1, "queued": 1}
    done = eng.run()
    assert len(done) == 2
    assert eng.pending() == {"active": 0, "queued": 0}


def _filled_cache(cfg, slots, max_len, kv_dtype, seed):
    """A cache of random contents, so that a row or state left alone and one
    overwritten differ."""
    leaves, tree = jax.tree_util.tree_flatten(
        transformer.init_cache(cfg, slots, max_len, kv_dtype))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = []
    for k, leaf in zip(keys, leaves):
        if leaf.dtype == jnp.int8:
            out.append(jax.random.randint(k, leaf.shape, -127, 128, jnp.int32).astype(jnp.int8))
        else:
            out.append(jax.random.uniform(k, leaf.shape, jnp.float32, 0.5, 1.5).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


# one call of the engine's decode program against the whole-cache commit it
# replaced: decode every slot, then ``where(mask, new, old)`` over every leaf
@pytest.mark.parametrize("arch,kv_dtype", [
    ("granite-3-2b", "bf16"), ("falcon-mamba-7b", "bf16"),
    ("jamba-1.5-large-398b", "bf16"), ("granite-3-2b", "int8")],
    ids=["gqa", "ssm", "hybrid", "int8_kv"])
@pytest.mark.parametrize("mask", [[False, False, True, False], [True, False, True, True]],
                         ids=["feed", "decode"])
def test_decode_commit_matches_whole_cache_commit(arch, kv_dtype, mask):
    cfg, params, eng = _engine(arch, slots=4, max_len=16)
    cache = _filled_cache(cfg, 4, 16, kv_dtype, seed=1)
    tokens = jnp.asarray([3, 1, 4, 1], jnp.int32)
    cur = jnp.asarray([5, 0, 9, 15], jnp.int32)
    mask = jnp.asarray(mask)

    @jax.jit
    def whole_cache_commit(params, cache, tokens, cur, mask):
        logits, new = transformer.decode_step(params, cfg, cache, tokens[:, None], cur)
        kept = jax.tree_util.tree_map(
            lambda n, o: jnp.where(mask.reshape((1, -1) + (1,) * (n.ndim - 2)), n, o),
            new, cache)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), kept

    want_tok, want = whole_cache_commit(params, cache, tokens, cur, mask)
    donated = jax.tree_util.tree_map(jnp.copy, cache)
    got_tok, got = eng._decode(params, donated, tokens, cur, mask)
    np.testing.assert_array_equal(np.asarray(got_tok), np.asarray(want_tok))
    flat_want = jax.tree_util.tree_flatten_with_path(want)[0]
    flat_got = jax.tree_util.tree_leaves(got)
    assert len(flat_got) == len(flat_want)
    for (path, w), g in zip(flat_want, flat_got):
        assert g.dtype == w.dtype, path
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=str(path))


def test_decode_aliases_the_cache():
    """The decode program writes its cache into the buffer it was given.

    On the CPU, at 4 slots x 256 of the reduced granite-3-2b (a 524,288 B
    cache), the program that returned a fresh cache read
    ``alias_size_in_bytes`` 0 and ``temp_size_in_bytes`` 414,384; the
    in-place one reads the cache's size and 400,048.  The program the TPU
    compiler makes at the serving cell's size is held in
    ``tests/test_tpu_compile.py``."""
    cfg, params, eng = _engine("granite-3-2b", slots=4, max_len=256)
    z = jnp.zeros(4, jnp.int32)
    ma = eng._decode.lower(params, eng.cache, z, z, jnp.ones(4, bool)).compile().memory_analysis()
    cache_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(eng.cache))
    assert cache_bytes == 524_288
    assert ma.alias_size_in_bytes == cache_bytes
    assert ma.temp_size_in_bytes < 407_216
