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


def test_feed_inputs_keep_their_values():
    """Nothing waits for a one-token feed before the engine writes the next
    token and length, so each feed must be given arrays of its own: the
    inputs of every feed's ``_decode`` call still hold, after the run, the
    values they had when the call was made."""
    _, _, eng = _engine("falcon-mamba-7b", slots=2)
    feeds, feeding = [], []
    decode, single_feed = eng._decode, eng._single_feed

    def keeping(params, cache, tokens, cur, mask):
        if feeding:
            feeds.append([(a, np.array(a)) for a in (tokens, cur, mask)])
        return decode(params, cache, tokens, cur, mask)

    def feed(slot):
        feeding.append(slot)
        single_feed(slot)
        feeding.pop()

    eng._decode, eng._single_feed = keeping, feed
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=3)
    eng.run()
    assert len(feeds) == eng.stats()["feed_calls"] == 7
    for args in feeds:
        for a, then in args:
            np.testing.assert_array_equal(np.asarray(a), then)


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


def test_prefill_aliases_the_cache():
    """The chunk program, like decode, writes its cache into the buffer it
    was given."""
    cfg, params, eng = _engine("granite-3-2b", slots=4, max_len=256)
    assert eng.chunk == 128
    ma = eng._prefill.lower(params, eng.cache, jnp.zeros((1, eng.chunk), jnp.int32),
                            jnp.int32(0), jnp.int32(0)).compile().memory_analysis()
    cache_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(eng.cache))
    assert ma.alias_size_in_bytes == cache_bytes


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_prefill_chunk_matches_per_token_feed(kv_dtype):
    """One chunk call puts into its slot's rows what one decode call per
    token puts there, and leaves every other slot's rows as they were."""
    cfg = get_config("granite-3-2b").reduced()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    slots, L, C, start, slot = 3, 32, 8, 5, 1
    cache = _filled_cache(cfg, slots, L, kv_dtype, seed=2)
    toks = jax.random.randint(jax.random.PRNGKey(3), (C,), 0, cfg.vocab_size)
    got = jax.jit(lambda c: transformer.prefill_chunk(
        params, cfg, c, toks[None], start, slot))(cache)
    mask = jnp.arange(slots) == slot
    feed = jax.jit(lambda c, t, cur: transformer.decode_step(
        params, cfg, c, t, cur, commit=mask)[1])
    want = cache
    for j in range(C):
        tokens = jnp.zeros((slots, 1), jnp.int32).at[slot, 0].set(toks[j])
        want = feed(want, tokens, jnp.full((slots,), start + j, jnp.int32))
    for (path, w), g in zip(jax.tree_util.tree_flatten_with_path(want)[0],
                            jax.tree_util.tree_leaves(got)):
        w, g = np.asarray(w, np.float32), np.asarray(g, np.float32)
        others = np.arange(slots) != slot
        np.testing.assert_array_equal(g[:, others], w[:, others], err_msg=str(path))
        # int8 rows may round one step apart where the two orders of
        # summation land either side of a rounding boundary
        atol = 1.0 if kv_dtype == "int8" and w.shape[-1] > 1 else 2e-3
        np.testing.assert_allclose(g[:, slot], w[:, slot], atol=atol, rtol=2e-3,
                                   err_msg=str(path))


def test_prefill_chunk_refuses_mamba_and_moe_layers():
    for arch in ("falcon-mamba-7b", "granite-moe-1b-a400m", "jamba-1.5-large-398b"):
        cfg = get_config(arch).reduced()
        assert not transformer.prefills_in_chunks(cfg)
        with pytest.raises(ValueError):
            transformer.prefill_chunk({}, cfg, {}, jnp.zeros((1, 4), jnp.int32), 0, 0)
    assert transformer.prefills_in_chunks(get_config("granite-3-2b").reduced())


def _record_decode_logits(eng) -> list:
    """Wrap the engine's decode program: each call first runs the same step
    without donating the cache, and records ``(call, uid, position, logits
    row)`` for every slot the call commits."""
    rows, calls = [], iter(range(1 << 30))
    step = jax.jit(lambda p, c, t, cur, m: transformer.decode_step(
        p, eng.cfg, c, t[:, None], cur, commit=m)[0])
    decode = eng._decode

    def recording(params, cache, tokens, cur, mask):
        lg, call = np.asarray(step(params, cache, tokens, cur, mask)), next(calls)
        for b in np.flatnonzero(np.asarray(mask)):
            rows.append((call, eng.active[b].uid, int(cur[b]), lg[b]))
        return decode(params, cache, tokens, cur, mask)

    eng._decode = recording
    return rows


def test_chunked_admission_matches_forward():
    """Every decode logit row the engine computes for a committed slot equals
    ``transformer.forward``'s at that position over prompt + served tokens.

    At 400 positions the chunk is 128 wide.  Prompts: 300 tokens (three
    chunks), 1 (no chunk call), 390 (its fourth chunk would pass the cache's
    end, so it starts at 272 and ends at 400) and 50 (one chunk, padded).
    On two slots the third and fourth are admitted while the other slot is
    mid-decode."""
    cfg, params, eng = _engine("granite-3-2b", slots=2, max_len=400)
    rng = np.random.default_rng(7)
    asks = [(300, 8), (1, 3), (390, 6), (50, 6)]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n, _ in asks]
    rows = _record_decode_logits(eng)
    uids = [eng.submit(p, max_new_tokens=m) for p, (_, m) in zip(prompts, asks)]
    done = {r.uid: r for r in eng.run()}
    assert sorted(done) == uids
    st = eng.stats()
    assert st["feed_calls"] == 0
    assert st["prefill_calls"] == 3 + 0 + 4 + 1
    assert st["prefill_tokens"] == sum(n - 1 for n, _ in asks)
    # admitted while the neighbour decodes: the third beside the first, the
    # fourth beside the third
    beside = {(a, b) for c, a, *_ in rows for d, b, *_ in rows if c == d and a != b}
    assert {(uids[0], uids[2]), (uids[3], uids[2])} <= beside
    for uid, prompt in zip(uids, prompts):
        req = done[uid]
        assert len(req.generated) == dict(zip(uids, asks))[uid][1]
        seq = np.concatenate([prompt, np.asarray(req.generated[:-1], np.int32)])
        want = np.asarray(transformer.forward(
            params, cfg, jnp.asarray(seq)[None], jnp.arange(len(seq))[None])[0])
        mine = [(pos, lg) for _, u, pos, lg in rows if u == uid]
        assert [pos for pos, _ in mine] == list(range(len(prompt) - 1, len(seq)))
        for pos, lg in mine:
            np.testing.assert_allclose(lg, want[pos], atol=2e-3, rtol=2e-3,
                                       err_msg=f"uid {uid} position {pos}")
