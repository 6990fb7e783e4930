"""Builders for the jit-able train / prefill / decode step functions.

``make_train_step`` is THE function the dry-run lowers and the autotuner's
real-measurement compiles: everything the SchedulePlan decides (sharding,
remat, microbatches, kernel tiles, optimizer dtype) is threaded through here.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.core.space import MeshSpec, SchedulePlan
from repro.kernels.ops import KernelTiles
from repro.models import transformer
from repro.models.losses import cross_entropy
from repro.models.moe import MoEDist
from repro.runtime import tracing
from repro.sharding.rules import ShardingRules, make_shard_fn
from repro.training import optimizer as optim


def tiles_from_plan(plan: SchedulePlan) -> KernelTiles:
    return KernelTiles(
        attn_block_q=plan.attn_block[0],
        attn_block_kv=plan.attn_block[1],
        scan_chunk=plan.scan_chunk,
    )


def moe_dist_for(cfg, shape, plan, mesh, mesh_spec) -> Optional[MoEDist]:
    """shard_map EP context when the plan asks for expert parallelism and the
    batch can shard over the data axes (see models/moe.py).

    REPRO_DISABLE_MOE_SHARDMAP=1 falls back to the jit global-sort dispatch
    (the §Perf baseline measurement path)."""
    import os

    if os.environ.get("REPRO_DISABLE_MOE_SHARDMAP"):
        return None
    if not (cfg.is_moe and plan.moe_mode == "ep" and mesh is not None and mesh_spec):
        return None
    if plan.param_strategy not in ("tp", "fsdp_tp", "tp2d"):
        return None
    if plan.batch_axes == "pod_data" and mesh_spec.multi_pod:
        batch_axes = ("pod", "data")
    else:
        batch_axes = ("data",)
    dp = 1
    for a in batch_axes:
        dp *= mesh_spec.axis(a)
    if shape.global_batch % dp != 0:
        return None
    if cfg.n_experts % min(mesh_spec.axis("model"), cfg.n_experts) != 0:
        return None
    return MoEDist(
        mesh=mesh,
        data_axes=batch_axes,
        fsdp=plan.param_strategy in ("fsdp_tp", "tp2d"),
    )


def make_positions(cfg: ModelConfig, batch: int, seq: int) -> jax.Array:
    if cfg.pos_kind == "mrope":
        return jnp.broadcast_to(
            jnp.arange(seq, dtype=jnp.int32)[None, None, :], (batch, 3, seq)
        )
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None, :], (batch, seq))


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------
def make_loss_fn(
    cfg: ModelConfig,
    shape: InputShape,
    plan: SchedulePlan,
    mesh: Optional[Mesh] = None,
    mesh_spec: Optional[MeshSpec] = None,
    unroll: bool = False,
) -> Callable:
    """(params, inputs, labels, positions) -> next-token loss: the function
    ``make_train_step`` differentiates."""
    tiles = tiles_from_plan(plan)
    rules = ShardingRules(cfg, shape, plan, mesh_spec) if mesh_spec else None
    shard = make_shard_fn(mesh, rules)
    moe_dist = moe_dist_for(cfg, shape, plan, mesh, mesh_spec)

    def loss_fn(params, inputs, labels, positions):
        logits = transformer.forward(
            params,
            cfg,
            inputs,
            positions,
            tiles=tiles,
            shard=shard,
            remat=plan.remat,
            unroll=unroll,
            moe_dist=moe_dist,
        )
        with tracing.scope(tracing.LOSS):
            return cross_entropy(logits[:, :-1, :], labels[:, 1:])

    return loss_fn


def make_train_step(
    cfg: ModelConfig,
    shape: InputShape,
    plan: SchedulePlan,
    opt_cfg: Optional[optim.OptimizerConfig] = None,
    mesh: Optional[Mesh] = None,
    mesh_spec: Optional[MeshSpec] = None,
    unroll: bool = False,
) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``batch``: {"inputs": (B,S) or (B,S,d), "labels": (B,S), "positions": ...}
    ``unroll``: fully unroll layer/microbatch loops (dry-run FLOP accounting;
    see transformer.forward).
    """
    opt_cfg = opt_cfg or optim.OptimizerConfig(
        moment_dtype=plan.opt_dtype if plan.opt_dtype != "float32" else "float32"
    )
    n_mb = plan.microbatches
    grad_fn = jax.value_and_grad(
        make_loss_fn(cfg, shape, plan, mesh, mesh_spec, unroll)
    )

    def train_step(params, opt_state, batch):
        inputs, labels = batch["inputs"], batch["labels"]
        positions = batch["positions"]
        if n_mb > 1:
            B = inputs.shape[0]
            assert B % n_mb == 0, (B, n_mb)
            mb = B // n_mb
            r = lambda x: x.reshape((n_mb, mb) + x.shape[1:])
            mb_batches = (r(inputs), r(labels), r(positions))

            def acc_body(carry, xs):
                loss_acc, grads_acc = carry
                i, l, p = xs
                loss, grads = grad_fn(params, i, l, p)
                grads_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), grads_acc, grads
                )
                return (loss_acc + loss, grads_acc), None

            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (loss_sum, grads), _ = jax.lax.scan(
                acc_body,
                (jnp.zeros((), jnp.float32), zero_grads),
                mb_batches,
                unroll=n_mb if unroll else 1,
            )
            loss = loss_sum / n_mb
            grads = jax.tree.map(lambda g: g / n_mb, grads)
        else:
            loss, grads = grad_fn(params, inputs, labels, positions)

        if plan.grad_comm == "int8":
            # fake-quant on the DP-reduced gradient: preserves the numerics of
            # the compressed collective; the wire-level int8 ring lives in
            # training/grad_compress.py (shard_map) for pure-DP plans.
            grads = jax.tree.map(_fake_quant_rowwise, grads)

        params, opt_state, opt_metrics = optim.apply_updates(
            params, grads, opt_state, opt_cfg
        )
        metrics = {"loss": loss, **opt_metrics}
        return params, opt_state, metrics

    return train_step


def _fake_quant_rowwise(g: jax.Array) -> jax.Array:
    if g.ndim < 2 or g.shape[-1] < 16:
        return g
    gf = g.astype(jnp.float32)
    amax = jnp.max(jnp.abs(gf), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return (jnp.round(gf / scale).clip(-127, 127) * scale).astype(g.dtype)


# ---------------------------------------------------------------------------
# Serve steps (prefill / decode)
# ---------------------------------------------------------------------------
def make_prefill_step(
    cfg: ModelConfig,
    shape: InputShape,
    plan: SchedulePlan,
    mesh: Optional[Mesh] = None,
    mesh_spec: Optional[MeshSpec] = None,
    unroll: bool = False,
) -> Callable:
    """(params, batch) -> logits for the full prompt (inference forward)."""
    tiles = tiles_from_plan(plan)
    rules = ShardingRules(cfg, shape, plan, mesh_spec) if mesh_spec else None
    shard = make_shard_fn(mesh, rules)

    moe_dist = moe_dist_for(cfg, shape, plan, mesh, mesh_spec)

    def prefill_step(params, batch):
        return transformer.forward(
            params,
            cfg,
            batch["inputs"],
            batch["positions"],
            tiles=tiles,
            shard=shard,
            remat="none",
            unroll=unroll,
            moe_dist=moe_dist,
        )

    return prefill_step


def make_serve_step(
    cfg: ModelConfig,
    shape: InputShape,
    plan: SchedulePlan,
    mesh: Optional[Mesh] = None,
    mesh_spec: Optional[MeshSpec] = None,
    unroll: bool = False,
) -> Callable:
    """(params, cache, inputs, cur, commit=None) -> (logits, cache): one
    decode token (``transformer.decode_step``)."""
    tiles = tiles_from_plan(plan)
    rules = ShardingRules(cfg, shape, plan, mesh_spec) if mesh_spec else None
    shard = make_shard_fn(mesh, rules)

    moe_dist = moe_dist_for(cfg, shape, plan, mesh, mesh_spec)

    def serve_step(params, cache, inputs, cur, commit=None):
        return transformer.decode_step(
            params, cfg, cache, inputs, cur, commit=commit, tiles=tiles,
            shard=shard, unroll=unroll, moe_dist=moe_dist,
        )

    return serve_step


# ---------------------------------------------------------------------------
# Sharding entries for jit(in_shardings/out_shardings)
# ---------------------------------------------------------------------------
def shardings_for_train(
    cfg, shape, plan, mesh: Mesh, mesh_spec: MeshSpec, params, opt_state
):
    rules = ShardingRules(cfg, shape, plan, mesh_spec)
    pspecs = rules.param_pspecs(params)
    ospecs = optim.opt_state_pspecs(opt_state, pspecs)
    ns = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree,
        is_leaf=lambda x: isinstance(x, P),
    )
    batch_specs = {
        "inputs": rules.batch_spec(3 if cfg.input_kind == "embeddings" else 2),
        "labels": rules.batch_spec(2),
        "positions": rules.batch_spec(3 if cfg.pos_kind == "mrope" else 2),
    }
    return ns(pspecs), ns(ospecs), ns(batch_specs), rules
