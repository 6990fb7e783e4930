"""Mixture-of-Experts MLP: top-k routing, sort-based capacity dispatch.

Two execution paths:

* **jit path** (``dist=None``): sort tokens by expert globally, scatter into
  a capacity-padded (E, C, d) buffer, grouped GEMM, weighted combine.
  Correct everywhere, but under SPMD the global argsort forces XLA to
  gather the full token array to every device — measured 142 s of
  collectives per step for phi-3.5-MoE on the 256-chip mesh
  (EXPERIMENTS.md §Perf iteration 1).

* **shard_map EP path** (``dist`` given, the beyond-paper optimization):
  routing and sort stay LOCAL to each data shard (argsort over T/dp
  tokens, no collective); every model rank holds E/ep experts and simply
  slices its experts' rows out of the locally-grouped buffer (tokens are
  replicated over the model axis, so no dispatch all-to-all is needed at
  all); the only cross-device traffic is one psum of the (T_local, d)
  partial outputs over the expert axis per layer — the same wire cost as
  a single TP all-reduce.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.kernels.ops import KernelTiles
from repro.models import layers
from repro.runtime import tracing

CAPACITY_FACTOR = 1.25


@dataclass(frozen=True)
class MoEDist:
    """Distribution context for the shard_map expert-parallel path."""

    mesh: Mesh
    model_axis: str = "model"
    data_axes: Tuple[str, ...] = ("data",)
    fsdp: bool = False  # expert weights additionally sharded over data_axes


def capacity(n_tokens: int, cfg: ModelConfig, block: int = 8) -> int:
    """Static per-expert capacity, rounded up to the MoE GEMM tile."""
    c = int(n_tokens * cfg.experts_per_token * CAPACITY_FACTOR / cfg.n_experts)
    c = max(c, block)
    return ((c + block - 1) // block) * block


def init(cfg: ModelConfig, key) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    o_scale = 0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)
    p = {
        "router": layers.dense_init(ks[0], (d, E), jnp.float32),
        "w_up": layers.dense_init(ks[1], (E, d, f), dt),
        "w_down": layers.dense_init(ks[2], (E, f, d), dt, scale=o_scale),
    }
    if cfg.act == "swiglu":
        p["w_gate"] = layers.dense_init(ks[3], (E, d, f), dt)
    return p


def forward(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d)
    *,
    tiles: KernelTiles,
    shard: Callable[[jax.Array, str], jax.Array],
    dist: Optional[MoEDist] = None,
) -> jax.Array:
    if dist is not None:
        return _forward_ep_shard_map(p, cfg, x, tiles, dist)
    B, S, d = x.shape
    T = B * S
    k = cfg.experts_per_token
    E = cfg.n_experts
    C = capacity(T, cfg, block=tiles.moe_block_c if T >= tiles.moe_block_c else 8)

    xt = x.reshape(T, d)
    topw, topi = _route(xt, p["router"], k)  # (T, k)

    # --- sort-based dispatch ---
    grouped, (se, st, sw, keep, pos) = _dispatch(xt, topw, topi, E, C, x.dtype)
    with tracing.scope(tracing.MOE_DISPATCH):
        grouped = shard(grouped, "moe_ecd")

    # --- expert FFN (grouped GEMMs) ---
    with tracing.scope(tracing.MOE_EXPERTS):
        up = ops.moe_gemm(grouped, p["w_up"], tiles=tiles, shard=shard)
        if cfg.act == "swiglu":
            gate = ops.moe_gemm(grouped, p["w_gate"], tiles=tiles, shard=shard)
            hidden = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
        else:
            hidden = layers.activate(up.astype(jnp.float32), cfg.act)
        hidden = shard(hidden.astype(x.dtype), "moe_ecf")
        out = ops.moe_gemm(hidden, p["w_down"], tiles=tiles, shard=shard)  # (E, C, d)

    # --- combine ---
    y = _combine(out, se, st, sw, keep, pos, T)
    with tracing.scope(tracing.MOE_COMBINE):
        return shard(y.astype(x.dtype).reshape(B, S, d), "act_btd")


@tracing.scope(tracing.MOE_ROUTE)
def _route(xt, router, k: int):
    """Top-k routing: (T, d) -> renormalised weights and expert ids, (T, k)."""
    logits = xt.astype(jnp.float32) @ router  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)
    return topw / jnp.sum(topw, axis=-1, keepdims=True), topi


@tracing.scope(tracing.MOE_DISPATCH)
def _dispatch(xt, topw, topi, E: int, C: int, dtype):
    """Sort-based grouping: (T, d) -> (E, C, d) plus the bookkeeping to
    combine: (sorted_expert, sorted_token, sorted_weight, keep, pos)."""
    T, k = topi.shape
    flat_e = topi.reshape(-1)  # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = topw.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.bincount(flat_e, length=E)
    seg_start = jnp.cumsum(counts) - counts  # exclusive prefix
    pos = jnp.arange(T * k) - seg_start[se]  # rank within expert
    keep = pos < C
    pos = jnp.where(keep, pos, 0)
    grouped = jnp.zeros((E, C, xt.shape[1]), dtype)
    src = jnp.where(keep[:, None], xt[st], 0).astype(dtype)
    grouped = grouped.at[se, pos].add(src)  # dropped tokens add 0
    return grouped, (se, st, sw, keep, pos)


@tracing.scope(tracing.MOE_COMBINE)
def _combine(out, se, st, sw, keep, pos, T: int):
    """Each token's expert outputs, weighted and summed: (E, C, d) -> (T, d) f32."""
    gathered = out[se, pos] * sw[:, None].astype(out.dtype)
    gathered = jnp.where(keep[:, None], gathered, 0)
    return jnp.zeros((T, out.shape[-1]), jnp.float32).at[st].add(
        gathered.astype(jnp.float32))


# ---------------------------------------------------------------------------
# shard_map expert-parallel path
# ---------------------------------------------------------------------------
def _forward_ep_shard_map(
    p: dict, cfg: ModelConfig, x: jax.Array, tiles: KernelTiles, dist: MoEDist
) -> jax.Array:
    mesh = dist.mesh
    ep = mesh.shape[dist.model_axis]
    E, k = cfg.n_experts, cfg.experts_per_token
    assert E % ep == 0 or ep % E == 0, (E, ep)
    ep = min(ep, E)
    E_loc = E // ep
    B, S, d = x.shape

    w_up, w_down = p["w_up"], p["w_down"]
    w_gate = p.get("w_gate")
    router = p["router"]

    # in_specs mirror sharding/rules.py: experts over model, fsdp over data
    fs = dist.data_axes if dist.fsdp else None
    up_spec = P(dist.model_axis, None, fs)
    down_spec = P(dist.model_axis, fs, None)
    x_spec = P(dist.data_axes, None, None)

    def local_fn(x_loc, router_w, up, down, gate):
        # x_loc: (B/dp, S, d) — replicated over the model axis
        # up/gate: (E_loc, d, f[/dp]), down: (E_loc, f, d[/dp])
        if dist.fsdp:
            with tracing.scope(tracing.MOE_EXPERTS):
                up = jax.lax.all_gather(up, dist.data_axes, axis=2, tiled=True)
                down = jax.lax.all_gather(down, dist.data_axes, axis=1, tiled=True)
                if gate is not None:
                    gate = jax.lax.all_gather(gate, dist.data_axes, axis=2, tiled=True)
        Bl, Sl, dl = x_loc.shape
        T = Bl * Sl
        C = capacity(T, cfg, block=8)
        xt = x_loc.reshape(T, dl)
        topw, topi = _route(xt, router_w, k)
        grouped, (se, st, sw, keep, pos) = _dispatch(xt, topw, topi, E, C, x_loc.dtype)
        # each model rank owns experts [r*E_loc, (r+1)*E_loc): slice locally —
        # no dispatch collective (tokens replicated over the expert axis)
        r = jax.lax.axis_index(dist.model_axis)
        with tracing.scope(tracing.MOE_DISPATCH):
            mine = jax.lax.dynamic_slice_in_dim(grouped, r * E_loc, E_loc, axis=0)

        with tracing.scope(tracing.MOE_EXPERTS):
            up_o = ops.moe_gemm(mine, up, tiles=tiles)
            if gate is not None:
                g_o = ops.moe_gemm(mine, gate, tiles=tiles)
                hidden = jax.nn.silu(g_o.astype(jnp.float32)) * up_o.astype(jnp.float32)
            else:
                hidden = layers.activate(up_o.astype(jnp.float32), cfg.act)
            out = ops.moe_gemm(hidden.astype(x_loc.dtype), down, tiles=tiles)
        with tracing.scope(tracing.MOE_COMBINE):
            # scatter back into the FULL (E, C, d) slot layout, zero elsewhere,
            # so the combine below can index it uniformly; psum merges ranks.
            full = jnp.zeros((E, C, dl), out.dtype)
            full = jax.lax.dynamic_update_slice_in_dim(full, out, r * E_loc, axis=0)
            y = _combine(full, se, st, sw, keep, pos, T)
            # combine-AR in bf16: halves the wire bytes of the only EP collective
            # (each token's k experts live on ≤k ranks, so the sum has ≤k terms —
            # bf16 is ample; §Perf iteration 3)
            y = jax.lax.psum(y.astype(jnp.bfloat16), dist.model_axis)
            return y.astype(x_loc.dtype).reshape(Bl, Sl, dl)

    if w_gate is not None:
        fn = jax.shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(x_spec, P(None, None), up_spec, down_spec, up_spec),
            out_specs=x_spec,
            check_vma=False,
        )
        return fn(x, router, w_up, w_down, w_gate)
    fn = jax.shard_map(
        lambda xl, r, u, dn: local_fn(xl, r, u, dn, None),
        mesh=mesh,
        in_specs=(x_spec, P(None, None), up_spec, down_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    return fn(x, router, w_up, w_down)


def aux_loss(router_probs: jax.Array, topi: jax.Array, n_experts: int) -> jax.Array:
    """Switch-style load-balancing loss (optional, used by the trainer)."""
    T = router_probs.shape[0]
    me = jnp.mean(router_probs, axis=0)
    ce = jnp.bincount(topi.reshape(-1), length=n_experts) / topi.size
    return n_experts * jnp.sum(me * ce) * (T / T)
