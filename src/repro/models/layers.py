"""Shared layer primitives: norms, positional encodings, activations, init."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.runtime import tracing


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
@tracing.scope(tracing.NORM)
def norm(x: jax.Array, w: jax.Array, kind: str, eps: float = 1e-6,
         shard=None) -> jax.Array:
    if kind == "rmsnorm":
        return ops.rmsnorm(x, w, eps=eps, shard=shard)
    # layernorm (no bias, like most modern stacks)
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------
def activate(x: jax.Array, kind: str) -> jax.Array:
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu2":  # nemotron squared-ReLU
        r = jax.nn.relu(x)
        return r * r
    if kind == "silu":
        return jax.nn.silu(x)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Rotary embeddings (standard, partial, and Qwen2-VL multimodal M-RoPE)
# ---------------------------------------------------------------------------
def _rope_freqs(rot_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))


def _apply_rot(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, H, S, R); cos/sin: (B, 1, S, R/2)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def rope(
    x: jax.Array,  # (B, H, S, D)
    positions: jax.Array,  # (B, S) int32
    theta: float,
    rotary_pct: float = 1.0,
) -> jax.Array:
    D = x.shape[-1]
    rot_dim = int(D * rotary_pct)
    rot_dim -= rot_dim % 2
    freqs = _rope_freqs(rot_dim, theta)  # (rot_dim/2,)
    ang = positions.astype(jnp.float32)[:, None, :, None] * freqs  # (B,1,S,R/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xr, xp = x[..., :rot_dim], x[..., rot_dim:]
    xr = _apply_rot(xr.astype(jnp.float32), cos, sin).astype(x.dtype)
    return jnp.concatenate([xr, xp], axis=-1) if rot_dim < D else xr


def mrope(
    x: jax.Array,  # (B, H, S, D)
    positions: jax.Array,  # (B, 3, S) int32 — temporal / height / width
    theta: float,
    sections=(16, 24, 24),  # half-dim split (Qwen2-VL: 16+24+24 = 64 = D/2)
) -> jax.Array:
    D = x.shape[-1]
    half = D // 2
    assert sum(sections) == half, (sections, half)
    freqs = _rope_freqs(D, theta)  # (half,)
    # per-component angles, then stitch sections: (B, 3, S, half)
    ang = positions.astype(jnp.float32)[..., None] * freqs[None, None, None, :]
    parts = []
    off = 0
    for comp, sec in enumerate(sections):
        parts.append(ang[:, comp, :, off : off + sec])
        off += sec
    ang = jnp.concatenate(parts, axis=-1)[:, None, :, :]  # (B,1,S,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return _apply_rot(x.astype(jnp.float32), cos, sin).astype(x.dtype)


def sinusoidal_pe(positions: jax.Array, d_model: int) -> jax.Array:
    """(B, S) -> (B, S, d) classic transformer sinusoid (MusicGen)."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B,S,half)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def apply_positions(q, k, cfg, positions):
    """Rotate q/k according to cfg.pos_kind ('rope'/'mrope'); else identity."""
    if cfg.pos_kind == "rope":
        return (
            rope(q, positions, cfg.rope_theta, cfg.rotary_pct),
            rope(k, positions, cfg.rope_theta, cfg.rotary_pct),
        )
    if cfg.pos_kind == "mrope":
        hd = cfg.resolved_head_dim
        secs = _mrope_sections(hd)
        return (
            mrope(q, positions, cfg.rope_theta, secs),
            mrope(k, positions, cfg.rope_theta, secs),
        )
    return q, k


def _mrope_sections(head_dim: int):
    half = head_dim // 2
    if half == 64:
        return (16, 24, 24)  # Qwen2-VL published split
    t = half // 4
    rest = half - t
    h = rest // 2
    return (t, h, rest - h)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def dense_init(key, shape, dtype, scale: float = 0.02) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
