"""GQA attention block: train/prefill forward + KV-cache decode step."""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.kernels.ops import KernelTiles
from repro.models import layers
from repro.runtime import tracing


def init(cfg: ModelConfig, key) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    o_scale = 0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)
    return {
        "wq": layers.dense_init(ks[0], (d, H * hd), dt),
        "wk": layers.dense_init(ks[1], (d, Hkv * hd), dt),
        "wv": layers.dense_init(ks[2], (d, Hkv * hd), dt),
        "wo": layers.dense_init(ks[3], (H * hd, d), dt, scale=o_scale),
    }


def _project(p, x, cfg):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = (x @ p["wk"]).reshape(B, S, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = (x @ p["wv"]).reshape(B, S, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    return q, k, v


@tracing.scope(tracing.ATTENTION)
def forward(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d)
    positions: jax.Array,
    *,
    tiles: KernelTiles,
    shard: Callable[[jax.Array, str], jax.Array],
    return_kv: bool = False,
):
    B, S, _ = x.shape
    q, k, v = _project(p, x, cfg)
    q = shard(q, "act_bhsd")
    k = shard(k, "act_bkvsd")
    v = shard(v, "act_bkvsd")
    q, k = layers.apply_positions(q, k, cfg, positions)
    o = ops.attention(q, k, v, causal=True, tiles=tiles, shard=shard)  # (B,H,S,hd)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, -1)
    out = shard(o @ p["wo"], "act_btd")
    if return_kv:
        return out, (k, v)
    return out


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype, kv_dtype: str = "bf16") -> dict:
    hd = cfg.resolved_head_dim
    shape = (batch, cfg.n_kv_heads, max_len, hd)
    if kv_dtype == "int8":
        # rowwise (per b,h,position) symmetric int8 + f32 scale: halves the
        # decode memory-roofline term (the KV read dominates long-context
        # decode) at ~0.3% attention error
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_s": jnp.ones(shape[:-1] + (1,), jnp.float32),
            "v_s": jnp.ones(shape[:-1] + (1,), jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


@tracing.scope(tracing.KV_WRITE)
def _quant_kv(x: jax.Array):
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


@tracing.scope(tracing.ATTENTION)
def decode_step(
    p: dict,
    cfg: ModelConfig,
    cache: dict,
    x: jax.Array,  # (B, 1, d)
    cur: jax.Array,  # int32 position of the new token: scalar, or (B,) per-row
    *,
    shard: Callable[[jax.Array, str], jax.Array],
) -> Tuple[jax.Array, dict]:
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    cur = jnp.asarray(cur, jnp.int32)
    per_row = cur.ndim == 1  # continuous batching: each row at its own length

    @tracing.scope(tracing.KV_WRITE)
    def _write_at_cur(c, new):
        # KV write at the token position — per-row positions need a
        # per-row dynamic_update_slice (vmapped over the batch axis)
        if per_row:
            return jax.vmap(
                lambda cb, nb, pb: jax.lax.dynamic_update_slice(cb, nb, (0, pb, 0))
            )(c, new, cur)
        return jax.lax.dynamic_update_slice(c, new, (0, 0, cur, 0))

    q, k_new, v_new = _project(p, x, cfg)  # (B,H,1,hd), (B,Hkv,1,hd)
    pos = cur[:, None] if per_row else jnp.full((B, 1), cur, jnp.int32)
    if cfg.pos_kind == "mrope":
        pos = jnp.broadcast_to(pos[:, None, :], (B, 3, 1))
    q, k_new = layers.apply_positions(q, k_new, cfg, pos)
    int8_kv = "k_s" in cache
    new_cache = {}
    if int8_kv:
        kq, ks = _quant_kv(k_new)
        vq, vs = _quant_kv(v_new)
        kc = _write_at_cur(cache["k"], kq)
        vc = _write_at_cur(cache["v"], vq)
        kss = _write_at_cur(cache["k_s"], ks)
        vss = _write_at_cur(cache["v_s"], vs)
        kc = shard(kc, "kv_cache")
        vc = shard(vc, "kv_cache")
        new_cache = {"k": kc, "v": vc, "k_s": kss, "v_s": vss}
        # scales fold into the logits / probs (per b,h,t) — the int8 cache is
        # never dequantized to a full-width tensor
        k, v = kc, vc
        k_scale = kss[..., 0]  # (B, Hkv, S)
        v_scale = vss[..., 0]
    else:
        k = _write_at_cur(cache["k"], k_new.astype(cache["k"].dtype))
        v = _write_at_cur(cache["v"], v_new.astype(cache["v"].dtype))
        k = shard(k, "kv_cache")
        v = shard(v, "kv_cache")
        new_cache = {"k": k, "v": v}
        k_scale = v_scale = None
    # GQA-grouped masked attention over the full cache: query heads reshape
    # to (Hkv, groups) so the cache is NEVER repeated (a materialized
    # jnp.repeat was measured at 4e11 HBM bytes/device on deepseek decode —
    # §Perf). bf16 cache reads, f32 accumulation on the (tiny) logits.
    groups = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, cfg.n_kv_heads, groups, 1, hd)
    kk = k.astype(jnp.bfloat16) if k.dtype == jnp.int8 else k
    vv = v.astype(jnp.bfloat16) if v.dtype == jnp.int8 else v
    logits = jnp.einsum(
        "bkgqd,bktd->bkgqt", qg.astype(jnp.float32), kk.astype(jnp.float32)
    ) * (hd ** -0.5)
    if k_scale is not None:
        logits = logits * k_scale[:, :, None, None, :]
    t = jnp.arange(k.shape[2])
    lim = cur[:, None, None, None, None] if per_row else cur
    mask = t[None, None, None, None, :] <= lim
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    if v_scale is not None:
        probs = probs * v_scale[:, :, None, None, :]
    o = jnp.einsum(
        "bkgqt,bktd->bkgqd", probs, vv.astype(jnp.float32)
    ).astype(x.dtype)
    o = o.reshape(B, cfg.n_heads, 1, hd).transpose(0, 2, 1, 3).reshape(B, 1, -1)
    out = shard(o @ p["wo"], "act_btd")
    return out, new_cache
