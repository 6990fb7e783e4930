"""Mamba-1 block: causal conv + selective scan; O(1)-state decode step."""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops, ref
from repro.kernels.ops import KernelTiles
from repro.models import layers
from repro.runtime import tracing


def init(cfg: ModelConfig, key) -> dict:
    d, Di, N = cfg.d_model, cfg.d_inner, cfg.ssm_state
    dtr, K = cfg.resolved_dt_rank, cfg.conv_width
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.dtype)
    o_scale = 0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)
    # S4D-real initialization for A: A[d, n] = -(n + 1)
    a = jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (Di, N))
    p = {
        "in_proj": layers.dense_init(ks[0], (d, 2 * Di), dt),
        "conv_w": layers.dense_init(ks[1], (K, Di), dt, scale=0.1),
        "conv_b": jnp.zeros((Di,), dt),
        "x_proj": layers.dense_init(ks[2], (Di, dtr + 2 * N), dt),
        "dt_w": layers.dense_init(ks[3], (dtr, Di), dt),
        "dt_b": jnp.log(jnp.expm1(jnp.full((Di,), 0.01, jnp.float32))).astype(dt),
        "A_log": jnp.log(a),
        "Dp": jnp.ones((Di,), jnp.float32),
        "out_proj": layers.dense_init(ks[4], (Di, d), dt, scale=o_scale),
    }
    if cfg.ssm_input_norms:
        p.update(dt_norm=jnp.ones((dtr,), dt), b_norm=jnp.ones((N,), dt),
                 c_norm=jnp.ones((N,), dt))
    return p


def _conv_causal(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv over time. x: (B, L, Di), w: (K, Di)."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(K):  # K is 4: unrolled adds, fuses cleanly
        y = y + xp[:, i : i + x.shape[1], :].astype(jnp.float32) * w[i].astype(jnp.float32)
    return (y + b.astype(jnp.float32)).astype(x.dtype)


def _ssm_inputs(p: dict, xc: jax.Array, cfg: ModelConfig):
    dtr, N = cfg.resolved_dt_rank, cfg.ssm_state
    proj = xc @ p["x_proj"]  # (..., dtr + 2N)
    dt_raw, Bm, Cm = jnp.split(proj, [dtr, dtr + N], axis=-1)
    if cfg.ssm_input_norms:  # Jamba: each through its own RMSNorm, fused by XLA
        dt_raw, Bm, Cm = (ref.rmsnorm(v, p[n]) for v, n in
                          ((dt_raw, "dt_norm"), (Bm, "b_norm"), (Cm, "c_norm")))
    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) @ p["dt_w"].astype(jnp.float32)
        + p["dt_b"].astype(jnp.float32)
    )
    A = -jnp.exp(p["A_log"])
    return dt, A, Bm, Cm


def forward(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d)
    *,
    tiles: KernelTiles,
    shard: Callable[[jax.Array, str], jax.Array],
) -> jax.Array:
    xz = x @ p["in_proj"]  # (B, S, 2*Di)
    xz = shard(xz, "act_bti")
    xi, z = jnp.split(xz, 2, axis=-1)
    xc = jax.nn.silu(_conv_causal(xi, p["conv_w"], p["conv_b"]))
    dt, A, Bm, Cm = _ssm_inputs(p, xc, cfg)
    y = ops.selective_scan(
        xc, dt.astype(xc.dtype), A, Bm, Cm, p["Dp"], tiles=tiles, shard=shard
    )
    y = y * jax.nn.silu(z)
    return shard(y @ p["out_proj"], "act_btd")


def init_cache(cfg: ModelConfig, batch: int, dtype) -> dict:
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, cfg.d_inner), dtype),
        "ssm": jnp.zeros((batch, cfg.d_inner, cfg.ssm_state), jnp.float32),
    }


def decode_step(
    p: dict,
    cfg: ModelConfig,
    cache: dict,
    x: jax.Array,  # (B, 1, d)
    *,
    layer: jax.Array,
    commit: Optional[jax.Array],
    shard: Callable[[jax.Array, str], jax.Array],
) -> Tuple[jax.Array, dict]:
    """One token for every slot, from period ``layer`` of the stacked conv and
    SSM state (leading period axis).  A slot whose ``commit`` entry is False
    keeps its old state (``commit=None`` updates every slot).  Returns the
    block output and the updated stacked state."""
    state = {n: jax.lax.dynamic_index_in_dim(c, layer, keepdims=False)
             for n, c in cache.items()}
    xz = x[:, 0] @ p["in_proj"]  # (B, 2*Di)
    xi, z = jnp.split(xz, 2, axis=-1)
    # conv over (cached K-1 inputs, new input)
    window = jnp.concatenate([state["conv"], xi[:, None, :]], axis=1)  # (B,K,Di)
    w = p["conv_w"].astype(jnp.float32)
    xc = jnp.sum(window.astype(jnp.float32) * w[None], axis=1) + p["conv_b"].astype(
        jnp.float32
    )
    xc = jax.nn.silu(xc).astype(x.dtype)  # (B, Di)
    dt, A, Bm, Cm = _ssm_inputs(p, xc, cfg)
    new_state, y = ops.selective_scan_step(
        state["ssm"], xc, dt.astype(xc.dtype), A, Bm, Cm, p["Dp"]
    )
    y = y * jax.nn.silu(z)
    out = shard((y @ p["out_proj"])[:, None, :], "act_btd")
    new = {"conv": window[:, 1:, :], "ssm": new_state}
    if commit is not None:
        with tracing.scope(tracing.CACHE_COMMIT):
            new = {n: jnp.where(commit.reshape((-1,) + (1,) * (v.ndim - 1)), v, state[n])
                   for n, v in new.items()}
    return out, {n: jax.lax.dynamic_update_index_in_dim(cache[n], v, layer, 0)
                 for n, v in new.items()}
