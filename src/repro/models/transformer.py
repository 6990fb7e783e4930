"""Model composition: scan-over-periods stacks for all 10 architectures.

A model is a stack of ``n_periods`` copies of a heterogeneous *period* (the
``cfg.layer_plan()``): dense archs have a 1-layer period, Jamba an 8-layer
period (1 attention + 7 Mamba, MoE every other slot).  Parameters for each
period slot are stacked on a leading ``n_periods`` axis and consumed by
``jax.lax.scan`` — keeping the HLO size independent of depth (95-layer
DeepSeek compiles as fast as the 24-layer Granite) and making remat policies
apply uniformly per period.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.kernels.ops import KernelTiles, DEFAULT_TILES
from repro.models import attention, layers, mamba, moe
from repro.runtime import tracing

ShardFn = Callable[[jax.Array, str], jax.Array]


def _identity_shard(x: jax.Array, name: str) -> jax.Array:
    return x


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _mlp_init(cfg: ModelConfig, key) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    o_scale = 0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)
    p = {
        "w_up": layers.dense_init(ks[0], (d, f), dt),
        "w_down": layers.dense_init(ks[1], (f, d), dt, scale=o_scale),
    }
    if cfg.act == "swiglu":
        p["w_gate"] = layers.dense_init(ks[2], (d, f), dt)
    return p


def _block_init(cfg: ModelConfig, spec: LayerSpec, key) -> dict:
    ks = jax.random.split(key, 2)
    dt = jnp.dtype(cfg.dtype)
    p: dict = {"norm1": jnp.ones((cfg.d_model,), dt)}
    if spec.mixer == "attn":
        p["attn"] = attention.init(cfg, ks[0])
    else:
        p["mamba"] = mamba.init(cfg, ks[0])
    if spec.mlp != "none":
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        p["mlp"] = moe.init(cfg, ks[1]) if spec.mlp == "moe" else _mlp_init(cfg, ks[1])
    return p


def init_params(cfg: ModelConfig, key) -> dict:
    plan = cfg.layer_plan()
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)

    def init_period(pkey):
        pkeys = jax.random.split(pkey, len(plan))
        return {
            f"b{i}": _block_init(cfg, spec, pkeys[i]) for i, spec in enumerate(plan)
        }

    period_keys = jax.random.split(k_blocks, cfg.n_periods)
    blocks = jax.vmap(init_period)(period_keys)

    params = {
        "blocks": blocks,
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if cfg.input_kind == "tokens":
        params["embed"] = layers.dense_init(k_emb, (cfg.vocab_size, cfg.d_model), dt)
    if not cfg.tie_embeddings:
        params["head"] = layers.dense_init(k_head, (cfg.d_model, cfg.vocab_size), dt)
    return params


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------
@tracing.scope(tracing.MLP)
def _mlp_forward(p: dict, cfg: ModelConfig, x: jax.Array, shard: ShardFn) -> jax.Array:
    up = x @ p["w_up"]
    up = shard(up, "act_btf")
    if cfg.act == "swiglu":
        gate = shard(x @ p["w_gate"], "act_btf")
        h = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    else:
        h = layers.activate(up.astype(jnp.float32), cfg.act)
    return shard(h.astype(x.dtype) @ p["w_down"], "act_btd")


@tracing.scope(tracing.EMBED)
def _embed(params: dict, cfg: ModelConfig, inputs: jax.Array, positions) -> jax.Array:
    if cfg.input_kind == "tokens":
        h = params["embed"][inputs]  # (B, S, d)
    else:
        h = inputs.astype(jnp.dtype(cfg.dtype))
    if cfg.pos_kind == "sinusoidal":
        pos = positions if positions.ndim == 2 else positions[:, 0]
        h = h + layers.sinusoidal_pe(pos, cfg.d_model).astype(h.dtype)
    return h


@tracing.scope(tracing.LOGITS)
def _logits(params: dict, cfg: ModelConfig, h: jax.Array, shard: ShardFn) -> jax.Array:
    h = layers.norm(h, params["final_norm"], cfg.norm, shard=shard)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", h, params["embed"])
    else:
        logits = h @ params["head"]
    return shard(logits, "logits")


def _block_forward(
    bp: dict,
    spec: LayerSpec,
    cfg: ModelConfig,
    h: jax.Array,
    positions,
    tiles: KernelTiles,
    shard: ShardFn,
    moe_dist=None,
) -> jax.Array:
    hn = layers.norm(h, bp["norm1"], cfg.norm, shard=shard)
    if spec.mixer == "attn":
        mixed = attention.forward(
            bp["attn"], cfg, hn, positions, tiles=tiles, shard=shard
        )
    else:
        mixed = mamba.forward(bp["mamba"], cfg, hn, tiles=tiles, shard=shard)
    h = h + mixed
    if spec.mlp != "none":
        hn = layers.norm(h, bp["norm2"], cfg.norm, shard=shard)
        if spec.mlp == "moe":
            out = moe.forward(bp["mlp"], cfg, hn, tiles=tiles, shard=shard,
                              dist=moe_dist)
        else:
            out = _mlp_forward(bp["mlp"], cfg, hn, shard)
        h = h + out
    return shard(h, "act_btd")


_REMAT_POLICIES = {
    "dots": "dots_with_no_batch_dims_saveable",
    "full": "nothing_saveable",
}


def _maybe_remat(fn, remat: str):
    if remat == "none":
        return fn
    policy = getattr(jax.checkpoint_policies, _REMAT_POLICIES[remat])
    return jax.checkpoint(fn, policy=policy, prevent_cse=False)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------
def forward(
    params: dict,
    cfg: ModelConfig,
    inputs: jax.Array,  # (B,S) tokens or (B,S,d) embeddings
    positions: jax.Array,  # (B,S) or (B,3,S) for mrope
    *,
    tiles: KernelTiles = DEFAULT_TILES,
    shard: ShardFn = _identity_shard,
    remat: str = "none",
    unroll: bool = False,
    moe_dist=None,
) -> jax.Array:
    """``unroll=True`` fully unrolls the period scan: required by the
    dry-run because XLA's ``cost_analysis`` does not fold while-loop trip
    counts into FLOPs (verified; see EXPERIMENTS.md §Dry-run notes)."""
    plan = cfg.layer_plan()
    h = shard(_embed(params, cfg, inputs, positions), "act_btd")

    def period_body(h, period_params):
        for i, spec in enumerate(plan):
            h = _block_forward(
                period_params[f"b{i}"], spec, cfg, h, positions, tiles, shard,
                moe_dist,
            )
        return h, None

    body = _maybe_remat(period_body, remat)
    h, _ = jax.lax.scan(
        body, h, params["blocks"], unroll=cfg.n_periods if unroll else 1
    )
    return _logits(params, cfg, h, shard)


# ---------------------------------------------------------------------------
# Decode (serve_step) with per-slot caches
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int, kv_dtype: str = "bf16") -> dict:
    """Stacked (n_periods leading dim) cache matching the block structure."""
    plan = cfg.layer_plan()
    dt = jnp.dtype(cfg.dtype)

    def one_period(_key):
        out = {}
        for i, spec in enumerate(plan):
            if spec.mixer == "attn":
                out[f"b{i}"] = attention.init_cache(cfg, batch, max_len, dt, kv_dtype)
            else:
                out[f"b{i}"] = mamba.init_cache(cfg, batch, dt)
        return out

    caches = jax.vmap(one_period)(jnp.arange(cfg.n_periods))
    return caches


def decode_step(
    params: dict,
    cfg: ModelConfig,
    cache: dict,
    inputs: jax.Array,  # (B,1) token or (B,1,d) embedding
    cur: jax.Array,  # int32 position of the new token: scalar, or (B,) per-row
    *,
    commit: Optional[jax.Array] = None,
    tiles: KernelTiles = DEFAULT_TILES,
    shard: ShardFn = _identity_shard,
    unroll: bool = False,
    moe_dist=None,
) -> Tuple[jax.Array, dict]:
    """One token for every slot.  ``commit`` (``(B,)`` bool) names the slots
    whose cache takes the token; the others keep theirs.  The default commits
    every slot.

    The period scan only reads the attention caches (by index, not as the
    scan's ``xs``) and returns each period's new K/V rows, which
    ``attention.write_rows`` then writes for every period at once; the Mamba
    state is the scan's carry, each period's written back in place.  So a
    cache the caller donates is updated in place.
    """
    plan = cfg.layer_plan()
    cur = jnp.asarray(cur, jnp.int32)
    pos = (
        cur[:, None] if cur.ndim == 1
        else jnp.broadcast_to(cur, (inputs.shape[0], 1)).astype(jnp.int32)
    )
    h = shard(_embed(params, cfg, inputs, pos), "act_btd")
    kv = {f"b{i}": cache[f"b{i}"] for i, spec in enumerate(plan) if spec.mixer == "attn"}
    state = {n: c for n, c in cache.items() if n not in kv}

    def period_body(carry, xs):
        h, state = carry
        period_params, layer = xs
        state, rows = dict(state), {}
        for i, spec in enumerate(plan):
            n = f"b{i}"
            bp = period_params[n]
            hn = layers.norm(h, bp["norm1"], cfg.norm, shard=shard)
            if spec.mixer == "attn":
                mixed, rows[n] = attention.decode_step(
                    bp["attn"], cfg, kv[n], hn, cur, layer=layer, shard=shard
                )
            else:
                mixed, state[n] = mamba.decode_step(
                    bp["mamba"], cfg, state[n], hn, layer=layer, commit=commit,
                    shard=shard,
                )
            h = h + mixed
            if spec.mlp != "none":
                hn = layers.norm(h, bp["norm2"], cfg.norm, shard=shard)
                if spec.mlp == "moe":
                    out = moe.forward(bp["mlp"], cfg, hn, tiles=tiles,
                                      shard=shard, dist=moe_dist)
                else:
                    out = _mlp_forward(bp["mlp"], cfg, hn, shard)
                h = h + out
        return (h, state), rows

    (h, state), rows = jax.lax.scan(
        period_body, (h, state), (params["blocks"], jnp.arange(cfg.n_periods)),
        unroll=cfg.n_periods if unroll else 1,
    )
    cache = {**state, **{n: attention.write_rows(kv[n], rows[n], cur, commit)
                         for n in kv}}
    logits = _logits(params, cfg, h[:, -1, :], shard)  # (B, V)
    return logits, cache


def prefills_in_chunks(cfg: ModelConfig) -> bool:
    """Whether ``prefill_chunk`` runs ``cfg``: every mixer is attention and
    every MLP dense.  A Mamba mixer would need the chunk's final conv/SSM
    state, which ``mamba.forward`` does not return; an MoE layer would route
    a chunk's padding into the experts' capacity."""
    return all(s.mixer == "attn" and s.mlp != "moe" for s in cfg.layer_plan())


def prefill_chunk(
    params: dict,
    cfg: ModelConfig,
    cache: dict,
    tokens: jax.Array,  # (1, C): one slot's prompt tokens
    start: jax.Array,  # int32 position of tokens[0, 0]
    slot: jax.Array,  # int32
) -> dict:
    """Put C prompt tokens of one slot into its cache at positions
    ``start .. start+C-1`` and return the cache; no logits.

    ``decode_step``'s arithmetic for C query rows (``attention.prefill_chunk``):
    the period scan only reads the cache, and the rows of every period go in
    after it with one in-place write per leaf, so a donated cache is updated
    in place.  ``start + C`` must not pass the cache's length: the write's
    start index would be clamped and the rows shifted.  Only for configs
    ``prefills_in_chunks`` admits, on one device.
    """
    if not prefills_in_chunks(cfg):
        raise ValueError(f"{cfg.name}: prefill_chunk needs attention mixers and dense MLPs")
    plan = cfg.layer_plan()
    shard = _identity_shard
    start = jnp.asarray(start, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    pos = start + jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
    h = _embed(params, cfg, tokens, pos)

    def period_body(h, xs):
        period_params, layer = xs
        rows = {}
        for i, spec in enumerate(plan):
            n = f"b{i}"
            bp = period_params[n]
            hn = layers.norm(h, bp["norm1"], cfg.norm, shard=shard)
            mixed, rows[n] = attention.prefill_chunk(
                bp["attn"], cfg, cache[n], hn, start, slot, layer=layer, shard=shard
            )
            h = h + mixed
            if spec.mlp != "none":
                hn = layers.norm(h, bp["norm2"], cfg.norm, shard=shard)
                h = h + _mlp_forward(bp["mlp"], cfg, hn, shard)
        return h, rows

    _, rows = jax.lax.scan(period_body, h, (params["blocks"], jnp.arange(cfg.n_periods)))
    with tracing.scope(tracing.KV_WRITE):
        return {n: {leaf: jax.lax.dynamic_update_slice(c, rows[n][leaf], (0, slot, 0, start, 0))
                    for leaf, c in cache[n].items()}
                for n in cache}
