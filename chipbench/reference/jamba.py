"""Plain float32 reference of the Jamba hybrid decoder (Mamba-1 + attention).

Written from the published architecture (transformers' ``JambaMambaMixer``,
``JambaAttention`` and ``JambaMLP``): pre-norm RMSNorm blocks, each period
of ``attn_layer_period`` layers with one attention layer at
``attn_layer_offset`` and Mamba-1 mixers at the other slots, a dense SwiGLU
MLP in every layer, a final RMSNorm and the tied embedding as the output
projection.  The Mamba mixer is

    x, z = in_proj(h);  x = silu(causal depthwise conv(x) + bias)
    dt, B, C = split(x_proj(x));  dt, B, C = RMSNorm each (own weight)
    dt = softplus(dt_proj(dt) + dt_bias);  A = -exp(A_log)
    s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t;  y_t = C_t . s_t + D x_t
    out = out_proj(y * silu(z))

and attention is causal GQA with no positional encoding, scaled by
head_dim ** -0.5.

Everything is ``jax.numpy`` in float32 with every matrix product at
``highest`` precision.  It imports nothing of the program and takes only the
benchmark's own weights (``chipbench.weights_jamba``) and token ids.  The
scan is a plain recurrence over time whose only carry is the
(batch, d_inner, d_state) state; attention and logits go in blocks of query
rows, so that 8,192 positions fit beside the weights.  ``mode="fp8"`` takes
every matrix product from float8 inputs (the control, as in ``granite``);
``attn_first`` and ``norms=False`` are the faults the comparison must catch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.granite import attention, mm, rms
from chipbench.weights_jamba import kinds


def scan(u, dt, A, Bm, Cm, D):
    """y_t = C_t . s_t + D u_t, s_t = exp(dt_t A) s_{t-1} + dt_t u_t B_t over
    u, dt (B, L, Di), B, C (B, L, N), A (Di, N), D (Di,)."""
    def step(s, inp):
        u_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t[..., None] * A) * s + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    s0 = jnp.zeros(u.shape[:1] + A.shape, jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(a.swapaxes(0, 1) for a in (u, dt, Bm, Cm)),
                        unroll=8)
    return y.swapaxes(0, 1) + u * D


def mamba(p, x, m, mode, norms=True):
    """The Mamba-1 mixer on x (B, S, d), float32."""
    S, d = x.shape[1], x.shape[2]
    Di, N, R = m["mamba_expand"] * d, m["mamba_d_state"], m["mamba_dt_rank"]
    K, eps = m["mamba_d_conv"], m["rms_norm_eps"]
    xz = mm("bsd,de->bse", x, p["in_proj"], mode)
    xi, z = xz[..., :Di], xz[..., Di:]
    xp = jnp.pad(xi, ((0, 0), (K - 1, 0), (0, 0)))
    xc = jax.nn.silu(sum(xp[:, k:k + S] * p["conv_w"][k] for k in range(K)) + p["conv_b"])
    proj = mm("bse,er->bsr", xc, p["x_proj"], mode)
    dt, Bm, Cm = proj[..., :R], proj[..., R:R + N], proj[..., R + N:]
    if norms:
        dt, Bm, Cm = (rms(dt, p["dt_norm"], eps), rms(Bm, p["b_norm"], eps),
                      rms(Cm, p["c_norm"], eps))
    dt = jax.nn.softplus(mm("bsr,re->bse", dt, p["dt_w"], mode) + p["dt_b"])
    y = scan(xc, dt, -jnp.exp(p["A_log"]), Bm, Cm, p["Dp"])
    return mm("bse,ed->bsd", y * jax.nn.silu(z), p["out_proj"], mode)


def self_attention(p, x, m, mode, q_block):
    B, S, _ = x.shape
    H, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = mm("bsd,de->bse", x, p["wq"], mode).reshape(B, S, H, D)
    k = mm("bsd,de->bse", x, p["wk"], mode).reshape(B, S, Hkv, D)
    v = mm("bsd,de->bse", x, p["wv"], mode).reshape(B, S, Hkv, D)
    o = attention(q, k, v, q_block, mode).reshape(B, S, H * D)
    return mm("bse,ed->bsd", o, p["wo"], mode)


def block(lp, h, kind, m, mode, q_block, norms):
    """One decoder layer on h (B, S, d), float32 throughout."""
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    eps = m["rms_norm_eps"]
    x = rms(h, lp["norm1"], eps)
    if kind == "attn":
        h = h + self_attention(lp["attn"], x, m, mode, q_block)
    else:
        h = h + mamba(lp["mamba"], x, m, mode, norms)
    x = rms(h, lp["norm2"], eps)
    p = lp["mlp"]
    return h + mm("bsf,fd->bsd", jax.nn.silu(mm("bsd,df->bsf", x, p["w_gate"], mode))
                  * mm("bsd,df->bsf", x, p["w_up"], mode), p["w_down"], mode)


def hidden(params, tokens, m, mode="f32", q_block=512, attn_first=False, norms=True):
    """Final normed hidden states (B, S, d) for token ids (B, S)."""
    S = tokens.shape[1]
    h = params["embed"][tokens].astype(jnp.float32)

    def period(h, pp):
        for i, kind in kinds(m, attn_first):
            h = block(pp[f"b{i}"], h, kind, m, mode, min(q_block, S), norms)
        return h, None

    h, _ = jax.lax.scan(period, h, params["blocks"])
    return rms(h, params["final_norm"].astype(jnp.float32), m["rms_norm_eps"])


def _worst(params, h, got_rows, rows):
    """Worst position's ||got - h E^T|| / ||h E^T|| over the logits, ``rows``
    positions at a time; ``got_rows(s0)`` gives the compared logits of
    positions s0..s0+rows, (B, rows, V)."""
    embed = params["embed"]

    def body(i, worst):
        s0 = i * rows
        ref = mm("bsd,vd->bsv", jax.lax.dynamic_slice_in_dim(h, s0, rows, 1), embed)
        num = jnp.sum(jnp.square(got_rows(s0) - ref), axis=-1)
        return jnp.maximum(worst, jnp.max(jnp.sqrt(num / jnp.sum(ref * ref, axis=-1))))

    return jax.lax.fori_loop(0, h.shape[1] // rows, body, jnp.float32(0))


def _rows(S: int) -> int:
    return next(r for r in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1) if S % r == 0)


@functools.partial(jax.jit, static_argnames=("m",))
def _rel_err(params, tokens, got, m):
    m = dict(m)
    h = hidden(params, tokens, m)
    rows = _rows(tokens.shape[1])
    return _worst(params, h, lambda s0: jax.lax.dynamic_slice_in_dim(
        got, s0, rows, 1).astype(jnp.float32), rows)


def logits_rel_err(params, tokens, got, m: dict) -> float:
    """Worst position's ||got - reference|| / ||reference|| over its logits,
    for the program's logits ``got`` (B, S, V) of token ids (B, S)."""
    with jax.default_matmul_precision("highest"):
        return float(_rel_err(params, tokens, got, tuple(sorted(m.items()))))


@functools.partial(jax.jit, static_argnames=("m", "mode", "attn_first", "norms"))
def _variant_err(params, tokens, m, mode, attn_first, norms):
    m = dict(m)
    h = hidden(params, tokens, m)
    hv = hidden(params, tokens, m, mode, attn_first=attn_first, norms=norms)
    rows = _rows(tokens.shape[1])
    return _worst(params, h, lambda s0: mm(
        "bsd,vd->bsv", jax.lax.dynamic_slice_in_dim(hv, s0, rows, 1), params["embed"], mode),
        rows)


def variant_rel_err(params, tokens, m: dict, mode="f32", attn_first=False,
                    norms=True) -> float:
    """``logits_rel_err`` of the reference computed with the control
    (``mode="fp8"``) or a fault in the program's place."""
    with jax.default_matmul_precision("highest"):
        return float(_variant_err(params, tokens, tuple(sorted(m.items())), mode,
                                  attn_first, norms))


@functools.partial(jax.jit, static_argnames=("m",))
def _logits(params, tokens, m):
    return mm("bsd,vd->bsv", hidden(params, tokens, dict(m)), params["embed"])


def seq_logits(params, tokens, m: dict):
    """float32 logits (B, S, V) of every position of ``tokens`` (B, S), for
    small sizes (tests)."""
    with jax.default_matmul_precision("highest"):
        return _logits(params, tokens, tuple(sorted(m.items())))
