"""Flash attention forward Pallas-TPU kernel (causal, GQA).

Tiling: grid = (batch, q_heads, q_blocks, kv_blocks); the kv axis is the
innermost (sequential on TPU), so the online-softmax running max / sum /
accumulator live in VMEM scratch that persists across kv steps.  The MXU
sees (block_q × head_dim) @ (head_dim × block_kv) and
(block_q × block_kv) @ (block_kv × head_dim) matmuls — block sizes are
schedule-space knobs (multiples of 128 keep the MXU fully fed).

The matmuls take q, k and v in their own dtype (bf16 on the chip) and
accumulate in float32; the softmax statistics and accumulator stay float32.
Causal kv blocks fall into three kinds (``geometry.flash_block_split``):
blocks above the diagonal are skipped via ``pl.when`` and, through the
clamped k/v index_maps, fetch nothing — with block_q == block_kv this
halves the work, the structural analogue of the paper's "don't evaluate
children you will not use" observation (§5.3); blocks below it run without
a mask; only blocks that cross it build the causal mask.

GQA is handled in the k/v index_maps (q-head h reads kv-head h // group).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.geometry import (
    flash_block_full,
    flash_block_runs,
    flash_last_kv_block,
)

NEG_INF = -1e30


def _fwd_kernel(
    q_ref,  # (1, 1, block_q, D)
    k_ref,  # (1, 1, block_kv, D)
    v_ref,  # (1, 1, block_kv, D)
    o_ref,  # (1, 1, block_q, D)
    m_ref,  # scratch (block_q, 1) f32
    l_ref,  # scratch (block_q, 1) f32
    acc_ref,  # scratch (block_q, D) f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_kv: int,
    q_off: int,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nkv = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(masked: bool):
        # MXU operands stay in the inputs' dtype; products accumulate in f32.
        q = q_ref[0, 0]
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)  # (bq, D)
        k = k_ref[0, 0]  # (bkv, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bkv)
        if masked:
            qpos = q_off + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0
            )
            kpos = kj * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1
            )
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]  # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (bq, bkv)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    if causal:
        # Blocks wholly below the diagonal run unmasked, blocks that cross it
        # run masked, blocks above it run nothing (geometry.flash_block_split).
        full = flash_block_full(qi, kj, q_off, block_q, block_kv)
        runs = flash_block_runs(qi, kj, q_off, block_q, block_kv)
        pl.when(full)(lambda: body(masked=False))
        pl.when(jnp.logical_and(runs, jnp.logical_not(full)))(
            lambda: body(masked=True)
        )
    else:
        body(masked=False)

    @pl.when(kj == nkv - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, 0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Skv, D)
    v: jax.Array,  # (B, Hkv, Skv, D)
    *,
    causal: bool = True,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, block_q, Skv, block_kv)
    scale = D ** -0.5
    grid = (B, Hq, Sq // block_q, Skv // block_kv)

    q_off = Skv - Sq
    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_kv=block_kv,
        q_off=q_off,
    )

    def kv_index(b, h, qi, kj):
        # Past the last block q block qi needs, keep that block's index: the
        # pipeline fetches nothing for the skipped steps.
        if causal:
            kj = jnp.minimum(kj, flash_last_kv_block(qi, q_off, block_q, block_kv))
        return (b, h // group, kj, 0)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, kj: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, D), kv_index),
            pl.BlockSpec((1, 1, block_kv, D), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, qi, kj: (b, h, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)


# re-exported from the jax-free geometry module (the cost model and the
# search workers import it from there without touching jax)
from repro.kernels.geometry import flash_vmem_bytes as vmem_bytes  # noqa: E402
