"""Per-architecture smoke tests: reduced config, one forward + one train
step + one decode step on CPU; asserts shapes and absence of NaNs.
The FULL configs are exercised only via the dry-run (ShapeDtypeStruct)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import InputShape
from repro.core.space import SchedulePlan
from repro.models import transformer
from repro.models.losses import cross_entropy
from repro.training import optimizer as optim
from repro.training.train_step import make_train_step

B, S = 2, 32

# the biggest reduced configs still take tens of seconds of XLA compile on
# CPU — run them in the slow lane, keep the small archs in tier-1
_HEAVY = {"jamba-1.5-large-398b", "falcon-mamba-7b", "qwen2-vl-72b",
          "musicgen-large"}
ARCHS_TIERED = [
    pytest.param(a, marks=pytest.mark.slow) if a in _HEAVY else a
    for a in ARCH_IDS
]


def _inputs(cfg, key):
    if cfg.input_kind == "tokens":
        inputs = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    else:
        inputs = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)
    if cfg.pos_kind == "mrope":
        pos = jnp.broadcast_to(jnp.arange(S)[None, None, :], (B, 3, S)).astype(jnp.int32)
    else:
        pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S)).astype(jnp.int32)
    labels = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    return inputs, pos, labels


@pytest.mark.parametrize("arch", ARCHS_TIERED)
def test_forward_shapes_no_nans(arch, rng_key):
    cfg = get_config(arch).reduced()
    params = transformer.init_params(cfg, rng_key)
    inputs, pos, _ = _inputs(cfg, rng_key)
    logits = transformer.forward(params, cfg, inputs, pos)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())


@pytest.mark.parametrize("arch", ARCHS_TIERED)
def test_train_step_no_nans(arch, rng_key):
    cfg = get_config(arch).reduced()
    shape = InputShape("t", S, B, "train")
    plan = SchedulePlan(microbatches=2, remat="dots", grad_comm="fp32")
    oc = optim.OptimizerConfig(peak_lr=1e-3, warmup_steps=2, total_steps=10)
    step = jax.jit(make_train_step(cfg, shape, plan, oc))
    params = transformer.init_params(cfg, rng_key)
    opt_state = optim.init_opt_state(params, oc)
    inputs, pos, labels = _inputs(cfg, rng_key)
    batch = {"inputs": inputs, "labels": labels, "positions": pos}
    params2, opt2, m = step(params, opt_state, batch)
    assert np.isfinite(float(m["loss"]))
    assert np.isfinite(float(m["grad_norm"]))
    # params actually changed
    delta = sum(
        float(jnp.sum(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2))
    )
    assert delta > 0


@pytest.mark.parametrize("arch,offset", [("jamba2-3b", 7), ("jamba-1.5-large-398b", 4),
                                         ("granite-3-2b", 0)])
def test_attention_sits_at_its_published_slot(arch, offset):
    """Jamba's configs put the period's attention layer at
    ``attn_layer_offset``; the other configs keep it at slot 0."""
    cfg = get_config(arch)
    plan = cfg.layer_plan()
    assert [i for i, s in enumerate(plan) if s.mixer == "attn"] == [offset]
    assert cfg.reduced().layer_plan() == plan


def test_jamba2_3b_parameter_count():
    # embedding 65536x2560; 26 Mamba mixers of 41,241,792 (in 2560x10240,
    # conv 4x5120 + 5120, x 5120x192, dt 160x5120 + 5120, A and D 5120x17,
    # out 5120x2560, dt/B/C norms 192); 2 attention layers of 13,762,560;
    # 28 MLPs of 62,914,560 and 28 x 2 norms of 2560; final norm 2560
    cfg = get_config("jamba2-3b")
    assert cfg.param_count() == (65536 * 2560 + 26 * 41_241_792 + 2 * 13_762_560
                                 + 28 * (62_914_560 + 2 * 2560) + 2560) == 3_029_337_472


@pytest.mark.slow  # token-by-token decode compiles T distinct step programs
@pytest.mark.parametrize(
    "arch", ["granite-3-2b", "falcon-mamba-7b", "jamba-1.5-large-398b"]
)
def test_decode_matches_forward(arch, rng_key):
    """The strongest cache-correctness check: token-by-token decode must
    reproduce the teacher-forced forward logits (validates KV cache update,
    Mamba conv/ssm state carry, position handling)."""
    cfg = get_config(arch).reduced()
    params = transformer.init_params(cfg, rng_key)
    T = 8
    toks = jax.random.randint(rng_key, (B, T), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T)).astype(jnp.int32)
    full_logits = transformer.forward(params, cfg, toks, pos)  # (B,T,V)
    cache = transformer.init_cache(cfg, B, T)
    last = None
    for t in range(T):
        last, cache = transformer.decode_step(
            params, cfg, cache, toks[:, t : t + 1], jnp.int32(t)
        )
    np.testing.assert_allclose(
        np.asarray(last), np.asarray(full_logits[:, -1, :]), atol=2e-3, rtol=2e-3
    )


@pytest.mark.parametrize(
    "arch", ["granite-3-2b", "falcon-mamba-7b", "jamba-1.5-large-398b"]
)
def test_per_slot_decode_matches_forward(arch, rng_key):
    """Decode as the serving engine runs it (one compiled step, a position
    per slot, slots at different lengths) reproduces the teacher-forced
    forward logits: each slot's new K/V row is seen by its own attention
    and lands in the cache, and the Mamba state carries."""
    cfg = get_config(arch).reduced()
    params = transformer.init_params(cfg, rng_key)
    T, lag = 8, 3  # slot 1 starts `lag` steps after slot 0
    toks = jax.random.randint(rng_key, (B, T), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T)).astype(jnp.int32)
    full_logits = transformer.forward(params, cfg, toks, pos)  # (B,T,V)
    step = jax.jit(lambda c, tok, cur, commit: transformer.decode_step(
        params, cfg, c, tok, cur, commit=commit))
    cache = transformer.init_cache(cfg, B, T)
    got = np.zeros((B, T, cfg.vocab_size), np.float32)
    for i in range(T + lag):
        cur = np.array([min(i, T - 1), max(i - lag, 0)], np.int32)
        commit = np.array([i < T, i >= lag])
        logits, cache = step(cache, toks[jnp.arange(B), cur][:, None], cur, commit)
        for b in range(B):
            if commit[b]:
                got[b, cur[b]] = np.asarray(logits[b])
    np.testing.assert_allclose(got, np.asarray(full_logits), atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("arch", ["granite-3-2b", "qwen2-vl-72b"])
def test_decode_int8_kv_close_to_bf16(arch, rng_key):
    cfg = get_config(arch).reduced()
    params = transformer.init_params(cfg, rng_key)
    if cfg.input_kind == "tokens":
        tok = jnp.array([[5], [7]])
    else:
        tok = jax.random.normal(rng_key, (B, 1, cfg.d_model))
    l1, _ = transformer.decode_step(
        params, cfg, transformer.init_cache(cfg, B, 16), tok, jnp.int32(0)
    )
    l2, _ = transformer.decode_step(
        params, cfg, transformer.init_cache(cfg, B, 16, "int8"), tok, jnp.int32(0)
    )
    assert float(jnp.max(jnp.abs(l1 - l2))) < 0.05


def test_unrolled_forward_matches_scanned(rng_key):
    cfg = get_config("granite-3-2b").reduced()
    params = transformer.init_params(cfg, rng_key)
    inputs, pos, _ = _inputs(cfg, rng_key)
    a = transformer.forward(params, cfg, inputs, pos, unroll=False)
    b = transformer.forward(params, cfg, inputs, pos, unroll=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_loss_decreases_quickly(rng_key):
    from repro.data.pipeline import Pipeline

    cfg = get_config("granite-3-2b").reduced()
    shape = InputShape("t", 64, 8, "train")
    plan = SchedulePlan(microbatches=1, remat="none")
    oc = optim.OptimizerConfig(peak_lr=1e-2, warmup_steps=5, total_steps=40)
    step = jax.jit(make_train_step(cfg, shape, plan, oc))
    params = transformer.init_params(cfg, rng_key)
    opt_state = optim.init_opt_state(params, oc)
    pipe = Pipeline(cfg, shape)
    losses = []
    for i in range(25):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses
