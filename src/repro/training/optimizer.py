"""AdamW from scratch (pure JAX) with optional int8-quantized moments.

Int8 moments (rowwise symmetric, dequant→update→requant each step) cut
optimizer-state HBM from 8 to 2 bytes/param — this is what lets the 398B
Jamba config fit a single 256-chip v5e pod (see EXPERIMENTS.md §Dry-run).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.runtime import tracing


@dataclass(frozen=True)
class OptimizerConfig:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    moment_dtype: str = "float32"  # float32 | int8


def lr_at(oc: OptimizerConfig, step: jax.Array) -> jax.Array:
    s = step.astype(jnp.float32)
    warm = s / max(oc.warmup_steps, 1)
    prog = jnp.clip(
        (s - oc.warmup_steps) / max(oc.total_steps - oc.warmup_steps, 1), 0.0, 1.0
    )
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return oc.peak_lr * jnp.where(s < oc.warmup_steps, warm, cos)


# -- int8 moment codecs -------------------------------------------------------
def _quantizable(leaf: jax.Array) -> bool:
    return leaf.ndim >= 2 and leaf.shape[-1] >= 16


def _mom_zero(leaf: jax.Array, oc: OptimizerConfig):
    if oc.moment_dtype == "int8" and _quantizable(leaf):
        return {
            "q": jnp.zeros(leaf.shape, jnp.int8),
            "s": jnp.zeros(leaf.shape[:-1] + (1,), jnp.float32),
        }
    return jnp.zeros(leaf.shape, jnp.float32)


def _mom_read(m) -> jax.Array:
    if isinstance(m, dict):
        return m["q"].astype(jnp.float32) * m["s"]
    return m


def _mom_write(val: jax.Array, like) :
    if isinstance(like, dict):
        amax = jnp.max(jnp.abs(val), axis=-1, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(val / scale), -127, 127).astype(jnp.int8)
        return {"q": q, "s": scale}
    return val


# -- public API ---------------------------------------------------------------
def init_opt_state(params, oc: OptimizerConfig) -> Dict[str, Any]:
    zeros = lambda: jax.tree.map(lambda p: _mom_zero(p, oc), params)
    return {"mu": zeros(), "nu": zeros(), "step": jnp.zeros((), jnp.int32)}


def global_norm(tree) -> jax.Array:
    sq = sum(
        jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree)
    )
    return jnp.sqrt(sq)


@tracing.scope(tracing.OPTIMIZER)
def apply_updates(
    params, grads, state, oc: OptimizerConfig
) -> Tuple[Any, Dict[str, Any]]:
    step = state["step"] + 1
    lr = lr_at(oc, step)
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, oc.clip_norm / (gnorm + 1e-9))
    bc1 = 1.0 - oc.b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - oc.b2 ** step.astype(jnp.float32)

    is_moment = lambda x: isinstance(x, dict) and set(x) == {"q", "s"}

    def upd(p, g, mu, nu):
        g = g.astype(jnp.float32) * scale
        m = oc.b1 * _mom_read(mu) + (1 - oc.b1) * g
        v = oc.b2 * _mom_read(nu) + (1 - oc.b2) * g * g
        mhat = m / bc1
        vhat = v / bc2
        delta = mhat / (jnp.sqrt(vhat) + oc.eps)
        if p.ndim >= 2:  # decoupled weight decay on matrices only
            delta = delta + oc.weight_decay * p.astype(jnp.float32)
        new_p = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
        return new_p, _mom_write(m, mu), _mom_write(v, nu)

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_mu = treedef.flatten_up_to(state["mu"])
    flat_nu = treedef.flatten_up_to(state["nu"])
    out = [upd(p, g, mu, nu) for p, g, mu, nu in zip(flat_p, flat_g, flat_mu, flat_nu)]
    new_params = treedef.unflatten([o[0] for o in out])
    new_mu = treedef.unflatten([o[1] for o in out])
    new_nu = treedef.unflatten([o[2] for o in out])
    new_state = {"mu": new_mu, "nu": new_nu, "step": step}
    metrics = {"lr": lr, "grad_norm": gnorm}
    return new_params, new_state, metrics


def opt_state_pspecs(state, param_pspecs):
    """Optimizer-state PartitionSpecs mirroring the param specs."""
    from jax.sharding import PartitionSpec as P

    def mom_spec(mspec):
        def f(m, pspec=mspec):
            return pspec

        return f

    def per_moment(mom_tree):
        flat_m, treedef = jax.tree.flatten(
            mom_tree, is_leaf=lambda x: isinstance(x, dict) and set(x) == {"q", "s"}
        )
        flat_spec = treedef.flatten_up_to(param_pspecs)
        out = []
        for m, spec in zip(flat_m, flat_spec):
            if isinstance(m, dict):
                out.append({"q": spec, "s": P(*spec[:-1], None)})
            else:
                out.append(spec)
        return treedef.unflatten(out)

    return {
        "mu": per_moment(state["mu"]),
        "nu": per_moment(state["nu"]),
        "step": P(),
    }
