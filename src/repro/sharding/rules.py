"""SchedulePlan → PartitionSpec rules for params, optimizer state,
activations, inputs, and caches.

Semantics:

* TP is active for a family iff ``param_strategy`` permits TP
  (``tp``/``fsdp_tp``) AND the family flag (``mixer_tp``/``ffn_tp``/
  ``vocab_shard``/``moe_mode``) asks for it.
* FSDP (ZeRO-3) shards every large weight's non-TP dim over the batch axes
  (``data`` or ``pod×data``).
* An axis is only assigned when the dim is divisible by the axis size —
  indivisible cases fall back to replicated on that axis (no silent padding).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.core.space import MeshSpec, SchedulePlan


def _axes_size(mesh: MeshSpec, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.axis(axes)
    n = 1
    for a in axes:
        n *= mesh.axis(a)
    return n


class ShardingRules:
    def __init__(
        self,
        cfg: ModelConfig,
        shape: InputShape,
        plan: SchedulePlan,
        mesh: MeshSpec,
    ):
        self.cfg = cfg
        self.shape = shape
        self.plan = plan
        self.mesh = mesh
        if plan.batch_axes == "pod_data" and mesh.multi_pod:
            self.batch = ("pod", "data")
        else:
            self.batch = ("data",)
        tp_on = plan.param_strategy in ("tp", "fsdp_tp", "tp2d")
        self.tp_mixer = tp_on and plan.mixer_tp
        self.tp_ffn = tp_on and plan.ffn_tp
        self.tp_vocab = tp_on and plan.vocab_shard
        # tp2d: inference-only 2D weight sharding (gather-on-use over the
        # batch axes) — same layout as ZeRO-3, no optimizer state involved
        self.fsdp = plan.param_strategy in ("fsdp", "fsdp_tp", "tp2d")
        self.fsdp_axes: Tuple[str, ...] = self.batch if self.fsdp else ()
        self.moe_mode = plan.moe_mode if tp_on or plan.moe_mode == "dense" else "dense"

    # -- helpers ---------------------------------------------------------------
    def _fit(self, axes, dim: int):
        """axes if dim divides by their product, else None (jit arguments
        demand exact divisibility; odd vocabs like 49155 stay unsharded)."""
        if not axes:
            return None
        if dim % _axes_size(self.mesh, axes) == 0:
            return axes if isinstance(axes, str) or len(axes) > 1 else axes[0]
        return None

    def _weight_spec(self, dims: Tuple[int, ...], tp_dim: Optional[int]) -> P:
        """Spec for one weight (without the stacked period axis)."""
        entries = [None] * len(dims)
        if tp_dim is not None:
            entries[tp_dim] = self._fit("model", dims[tp_dim])
        if self.fsdp_axes:
            # largest remaining divisible dim gets the ZeRO shard
            cand = sorted(
                (i for i in range(len(dims)) if entries[i] is None),
                key=lambda i: -dims[i],
            )
            for i in cand:
                fit = self._fit(self.fsdp_axes, dims[i])
                if fit is not None:
                    entries[i] = fit
                    break
        return P(*entries)

    # -- params ------------------------------------------------------------------
    def param_spec(self, path: Tuple[str, ...], shape: Tuple[int, ...]) -> P:
        stacked = path[0] == "blocks"
        dims = shape[1:] if stacked else shape
        name = path[-1]
        parent = path[-2] if len(path) >= 2 else ""
        tp_dim: Optional[int] = None

        if name in ("norm1", "norm2", "final_norm", "conv_b", "dt_b", "Dp"):
            spec = P(*([None] * len(dims)))
            if name in ("conv_b", "dt_b", "Dp") and self.tp_mixer:
                spec = P(self._fit("model", dims[0]))
        elif name == "embed":
            tp = self._fit("model", dims[0]) if self.tp_vocab else None
            fs = self._fit(self.fsdp_axes, dims[1])
            spec = P(tp, fs)
        elif name == "head":
            tp = self._fit("model", dims[1]) if self.tp_vocab else None
            fs = self._fit(self.fsdp_axes, dims[0])
            spec = P(fs, tp)
        elif parent == "attn":
            if self.tp_mixer:
                tp_dim = 0 if name == "wo" else 1
            spec = self._weight_spec(dims, tp_dim)
        elif parent == "mamba":
            if self.tp_mixer:
                tp_dim = {
                    "in_proj": 1,
                    "conv_w": 1,
                    "x_proj": 0,
                    "dt_w": 1,
                    "A_log": 0,
                    "out_proj": 0,
                }.get(name)
            spec = self._weight_spec(dims, tp_dim)
        elif parent == "mlp" and len(dims) == 3:  # MoE expert weights (E, d, f)
            if self.moe_mode == "ep":
                ep = self._fit("model", dims[0])
                fs = self._fit(self.fsdp_axes, dims[2] if name != "w_down" else dims[1])
                if name == "w_down":
                    spec = P(ep, fs, None)
                else:
                    spec = P(ep, None, fs)
            elif self.moe_mode == "tp":
                tp_dim = 1 if name == "w_down" else 2
                spec = self._weight_spec(dims, tp_dim)
            else:
                spec = self._weight_spec(dims, None)
        elif parent == "mlp":
            if name == "router":
                spec = P(*([None] * len(dims)))
            else:
                if self.tp_ffn:
                    tp_dim = 0 if name == "w_down" else 1
                spec = self._weight_spec(dims, tp_dim)
        else:
            spec = self._weight_spec(dims, None)

        if stacked:
            spec = P(None, *spec)
        return spec

    def param_pspecs(self, params) -> dict:
        def f(path, leaf):
            keys = tuple(
                k.key if hasattr(k, "key") else str(k) for k in path
            )
            return self.param_spec(keys, leaf.shape)

        return jax.tree_util.tree_map_with_path(f, params)

    def _b(self, dim: int):
        """Batch-dim entry: only shard when the dim divides (batch-1 decode
        leaves the data axis for the sequence dim instead)."""
        return self._fit(self.batch, dim)

    # -- activations ----------------------------------------------------------------
    def act_spec(self, name: str, ndim: int, shape: Tuple[int, ...]) -> Optional[P]:
        b = self._b(shape[0])
        plan = self.plan
        if name == "act_btd":
            seq = "model" if plan.seq_shard else None
            return P(b, self._fit(seq, shape[1]) if seq else None, None)
        if name == "act_bhsd":
            h = self._fit("model", shape[1]) if self.tp_mixer else None
            return P(b, h, None, None)
        if name == "act_bkvsd":
            h = self._fit("model", shape[1]) if self.tp_mixer else None
            return P(b, h, None, None)
        if name == "act_btf":
            f = self._fit("model", shape[2]) if self.tp_ffn else None
            return P(b, None, f)
        if name == "act_bti":
            i = self._fit("model", shape[2]) if self.tp_mixer else None
            return P(b, None, i)
        if name == "moe_ecd":
            if self.moe_mode == "ep":
                return P(self._fit("model", shape[0]), None, None)
            return P(None, None, None)
        if name == "moe_ecf":
            if self.moe_mode == "ep":
                return P(self._fit("model", shape[0]), None, None)
            if self.moe_mode == "tp":
                return P(None, None, self._fit("model", shape[2]))
            return P(None, None, None)
        if name == "logits":
            v = self._fit("model", shape[-1]) if self.tp_vocab else None
            return P(*([b] + [None] * (ndim - 2) + [v]))
        if name == "kv_cache":
            h = self._fit("model", shape[1]) if self.tp_mixer else None
            if plan.seq_shard and b is None:
                # batch-1 long-context: the whole mesh shards the sequence
                axes = tuple(self.batch) + ("model",) if h is None else self.batch
                return P(None, h, self._fit(axes, shape[2]), None)
            if h is None and plan.seq_shard:
                return P(b, None, self._fit("model", shape[2]), None)
            return P(b, h, None, None)
        return None

    # -- inputs / cache ---------------------------------------------------------------
    def batch_spec(self, ndim: int, batch_dim: Optional[int] = None) -> P:
        b = self._b(batch_dim if batch_dim is not None else self.shape.global_batch)
        return P(*([b] + [None] * (ndim - 1)))

    def cache_pspecs(self, cache) -> dict:
        """Stacked caches: leading period axis, then (B, ...)."""

        def f(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if name in ("k", "v", "k_s", "v_s"):
                inner = self.act_spec("kv_cache", leaf.ndim - 1, leaf.shape[1:])
                return P(None, *inner)
            # mamba conv/ssm states: shard batch; d_inner over model if TP
            b = self._b(leaf.shape[1])
            if name == "ssm":
                di = self._fit("model", leaf.shape[2]) if self.tp_mixer else None
                return P(None, b, di, None)
            if name == "conv":
                di = self._fit("model", leaf.shape[3]) if self.tp_mixer else None
                return P(None, b, None, di)
            return P(*([None] * leaf.ndim))

        return jax.tree_util.tree_map_with_path(f, cache)


    # -- Pallas kernels -------------------------------------------------------------
    def kernel_specs(self, kernel: str, *shapes) -> Tuple[Tuple[P, ...], P]:
        """(in_specs, out_spec) that split one Pallas call over the mesh
        along the dims the kernel is parallel in; every other dim is whole
        on each device."""
        if kernel == "rmsnorm":  # rows: (..., d), (d,)
            x = P(self._b(shapes[0][0]), *([None] * (len(shapes[0]) - 1)))
            return (x, P(None)), x
        if kernel == "attention":  # q (B,H,S,D), k/v (B,Hkv,S,D): batch, heads
            H, Hkv = shapes[0][1], shapes[1][1]
            h = "model" if (
                self.tp_mixer and self._fit("model", H) and self._fit("model", Hkv)
            ) else None
            qkv = P(self._b(shapes[0][0]), h, None, None)
            return (qkv, qkv, qkv), qkv
        if kernel == "selective_scan":  # u, dt, A, Bm, Cm, D: batch, d_inner
            b = self._b(shapes[0][0])
            i = self._fit("model", shapes[0][2]) if self.tp_mixer else None
            ui, bn = P(b, None, i), P(b, None, None)
            return (ui, ui, P(i, None), bn, bn, P(i)), ui
        if kernel == "moe_gemm":  # x (E,C,d), w (E,d,f): experts
            e = self._fit("model", shapes[0][0]) if self.moe_mode == "ep" else None
            ex = P(e, None, None)
            return (ex, ex), ex
        raise KeyError(kernel)


class ShardFn:
    """The ``shard(x, name)`` callback threaded through the models: it
    constrains activation ``name`` to its rule.  Its ``mesh`` and ``rules``
    let the kernel wrappers in ``kernels/ops.py`` run a Pallas call per
    device, since Mosaic kernels are not partitioned by XLA."""

    def __init__(self, mesh: Mesh, rules: ShardingRules):
        self.mesh = mesh
        self.rules = rules

    def __call__(self, x: jax.Array, name: str) -> jax.Array:
        spec = self.rules.act_spec(name, x.ndim, x.shape)
        if spec is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))


def make_shard_fn(mesh: Mesh, rules: Optional[ShardingRules]):
    """Returns the `shard(x, name)` callback threaded through the models."""
    if rules is None or mesh is None:
        return lambda x, name: x
    return ShardFn(mesh, rules)
