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


def _cache_rows(cache: dict, k_new: jax.Array, v_new: jax.Array) -> dict:
    """New K/V rows as the cache holds them: int8 with per-row scales where
    it has ``k_s``/``v_s``."""
    if "k_s" in cache:
        kq, ks = _quant_kv(k_new)
        vq, vs = _quant_kv(v_new)
        rows = {"k": kq, "v": vq, "k_s": ks, "v_s": vs}
    else:
        rows = {"k": k_new, "v": v_new}
    return {n: r.astype(cache[n].dtype) for n, r in rows.items()}


def _attend(p, cfg, q, kv, lim, shard, dtype) -> jax.Array:
    """Attention of ``q`` ``(B, H, Q, hd)`` over the K/V slab ``kv``
    ``(B, Hkv, L, w)``, key position ``t`` visible where ``t <= lim``
    (broadcast against ``(B, Hkv, groups, Q, L)``), and the output
    projection: ``(B, Q, d)``."""
    B, _, Q, hd = q.shape
    k = shard(kv["k"], "kv_cache")
    v = shard(kv["v"], "kv_cache")
    # int8: the scales fold into the logits / probs (per b,h,t), so the int8
    # cache is never dequantized to a full-width tensor
    k_scale = kv["k_s"][..., 0] if "k_s" in kv else None  # (B, Hkv, S)
    v_scale = kv["v_s"][..., 0] if "v_s" in kv else None
    # GQA-grouped masked attention over the full cache: query heads reshape
    # to (Hkv, groups) so the cache is NEVER repeated (a materialized
    # jnp.repeat was measured at 4e11 HBM bytes/device on deepseek decode —
    # §Perf). bf16 cache reads, f32 accumulation on the (tiny) logits.
    groups = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, cfg.n_kv_heads, groups, Q, hd)
    kk = k.astype(jnp.bfloat16) if k.dtype == jnp.int8 else k
    vv = v.astype(jnp.bfloat16) if v.dtype == jnp.int8 else v
    logits = jnp.einsum(
        "bkgqd,bktd->bkgqt", qg.astype(jnp.float32), kk.astype(jnp.float32)
    ) * (hd ** -0.5)
    if k_scale is not None:
        logits = logits * k_scale[:, :, None, None, :]
    t = jnp.arange(k.shape[2])
    mask = t[None, None, None, None, :] <= lim
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    if v_scale is not None:
        probs = probs * v_scale[:, :, None, None, :]
    o = jnp.einsum(
        "bkgqt,bktd->bkgqd", probs, vv.astype(jnp.float32)
    ).astype(dtype)
    o = o.reshape(B, cfg.n_heads, Q, hd).transpose(0, 2, 1, 3).reshape(B, Q, -1)
    return shard(o @ p["wo"], "act_btd")


@tracing.scope(tracing.ATTENTION)
def decode_step(
    p: dict,
    cfg: ModelConfig,
    cache: dict,
    x: jax.Array,  # (B, 1, d)
    cur: jax.Array,  # int32 position of the new token: scalar, or (B,) per-row
    *,
    layer: jax.Array,
    shard: Callable[[jax.Array, str], jax.Array],
) -> Tuple[jax.Array, dict]:
    """One token of attention for every slot against period ``layer`` of the
    stacked cache, which it only reads.

    ``cache`` holds this block's K/V for every period, ``(P, B, Hkv, L, hd)``
    (int8 K/V add per-row scales ``k_s``/``v_s``).  The new token's K/V row
    takes position ``cur`` in what the attention reads; the cache itself
    takes the rows later, all periods at once (``write_rows``).  Returns the
    block output and the new rows, ``(B, Hkv, 1, w)`` in the cache's dtypes.
    """
    B = x.shape[0]
    cur = jnp.asarray(cur, jnp.int32)
    per_row = cur.ndim == 1  # continuous batching: each row at its own length
    q, k_new, v_new = _project(p, x, cfg)  # (B,H,1,hd), (B,Hkv,1,hd)
    pos = cur[:, None] if per_row else jnp.full((B, 1), cur, jnp.int32)
    if cfg.pos_kind == "mrope":
        pos = jnp.broadcast_to(pos[:, None, :], (B, 3, 1))
    q, k_new = layers.apply_positions(q, k_new, cfg, pos)
    rows = _cache_rows(cache, k_new, v_new)
    with tracing.scope(tracing.KV_WRITE):
        # the slab as the cache will hold it: XLA fuses this select into the
        # attention's read of the slab
        t = jnp.arange(cache["k"].shape[3])
        at_cur = t[None, None, :, None] == (cur[:, None, None, None] if per_row else cur)
        kv = {n: jnp.where(at_cur, rows[n],
                           jax.lax.dynamic_index_in_dim(c, layer, keepdims=False))
              for n, c in cache.items()}
    lim = cur[:, None, None, None, None] if per_row else cur
    return _attend(p, cfg, q, kv, lim, shard, x.dtype), rows


@tracing.scope(tracing.ATTENTION)
def prefill_chunk(
    p: dict,
    cfg: ModelConfig,
    cache: dict,
    x: jax.Array,  # (1, C, d): C consecutive tokens of one slot's prompt
    start: jax.Array,  # int32 position of the chunk's first token
    slot: jax.Array,  # int32 slot whose cache the chunk extends
    *,
    layer: jax.Array,
    shard: Callable[[jax.Array, str], jax.Array],
) -> Tuple[jax.Array, dict]:
    """``decode_step`` for C query rows of one slot at positions
    ``start .. start+C-1``, against period ``layer`` of the stacked cache,
    which it only reads.

    The chunk's K/V rows take positions ``[start, start+C)`` of the slot's
    slab in what the attention reads, under the causal mask ``t <= start+j``;
    the caller keeps ``start + C`` within the cache and writes the rows.
    Returns the block output and the rows, ``(1, Hkv, C, w)`` in the cache's
    dtypes.
    """
    C = x.shape[1]
    start = jnp.asarray(start, jnp.int32)
    q, k_new, v_new = _project(p, x, cfg)  # (1,H,C,hd), (1,Hkv,C,hd)
    at = start + jnp.arange(C, dtype=jnp.int32)
    pos = at[None, :]
    if cfg.pos_kind == "mrope":
        pos = jnp.broadcast_to(pos[:, None, :], (1, 3, C))
    q, k_new = layers.apply_positions(q, k_new, cfg, pos)
    rows = _cache_rows(cache, k_new, v_new)
    with tracing.scope(tracing.KV_WRITE):
        kv = {}
        for n, c in cache.items():
            slab = jax.lax.dynamic_slice(c, (layer, slot, 0, 0, 0), (1, 1) + c.shape[2:])[0]
            kv[n] = jax.lax.dynamic_update_slice(slab, rows[n], (0, 0, start, 0))
    return _attend(p, cfg, q, kv, at.reshape(1, 1, 1, C, 1), shard, x.dtype), rows


def write_rows(
    cache: dict,
    rows: dict,  # per leaf (P, B, Hkv, 1, w): every period's new rows
    cur: jax.Array,
    commit: Optional[jax.Array],
) -> dict:
    """Write each slot's new rows, all periods at once, in place at
    ``(:, b, :, cur[b], :)`` of the stacked cache; a slot whose ``commit``
    entry is False keeps its old rows (``commit=None`` writes every slot).

    The rows go in as ``(P, n, Hkv, 1, w)`` blocks of n slots: one block of
    all slots when they share a position, else one per slot.  XLA then keeps
    the cache's own layout and updates it in place, where a scatter, or rows
    gathered into one array, makes it re-lay the whole cache out on TPU.
    """
    cur = jnp.asarray(cur, jnp.int32)
    P, B = rows["k"].shape[:2]
    blocks = ([(b, 1, (0, b, 0, cur[b], 0)) for b in range(B)] if cur.ndim == 1
              else [(0, B, (0, 0, 0, cur, 0))])
    out = {}
    for n, c in cache.items():
        for b, m, at in blocks:
            part = rows[n][:, b:b + m]
            if commit is not None:
                with tracing.scope(tracing.CACHE_COMMIT):
                    was = jax.lax.dynamic_slice(c, at, part.shape)
                    part = jnp.where(commit[b:b + m].reshape(1, m, 1, 1, 1), part, was)
            with tracing.scope(tracing.KV_WRITE):
                c = jax.lax.dynamic_update_slice(c, part, at)
        out[n] = c
    return out
