"""Plain float32 reference of the Granite decoder blocks the benchmark runs.

Written from the published architecture (pre-norm RMSNorm blocks, rotary
GQA attention, SwiGLU MLP or top-k routed SwiGLU experts, tied embedding),
in ``jax.numpy`` with every matrix product at ``highest`` precision.  It
imports nothing of the program and takes only the benchmark's own weights
(``chipbench.weights``) and token ids.  The departures of the program from
the published model that it shares are listed in each configuration file.

``mode="fp8"`` computes every matrix product from float8 inputs scaled
per tensor (e4m3 forward, e5m2 for the gradients), with float32
accumulation: the control that a lower-precision program would read like.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import flatten

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _q8(x, dtype=jnp.float8_e4m3fn):
    """x rounded to a float8 format after scaling its largest magnitude to
    the format's largest."""
    s = float(jnp.finfo(dtype).max) / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(dtype).astype(jnp.float32) / s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm8(spec, a, b):
    return jnp.einsum(spec, _q8(a), _q8(b), precision=HI)


def _mm8_fwd(spec, a, b):
    qa, qb = _q8(a), _q8(b)
    return jnp.einsum(spec, qa, qb, precision=HI), (qa, qb)


def _mm8_bwd(spec, res, g):
    """The backward products take float8 inputs too: e5m2 for the gradient,
    as float8 training does."""
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HI), *res)
    return vjp(_q8(g, jnp.float8_e5m2))


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def mm(spec: str, a, b, mode: str = "f32"):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "fp8":
        return _mm8(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HI)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """Rotate-half rotary embedding of x (B, S, H, D) at positions (B, S)."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, :, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v, q_block: int, mode: str):
    """Causal GQA attention; q (B,S,H,D), k/v (B,S,Hkv,D).  Queries go in
    blocks of ``q_block`` so the score matrix of one block fits."""
    B, S, H, D = q.shape
    g = H // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    outs = []
    for s0 in range(0, S, q_block):
        qb = q[:, s0:s0 + q_block]

        @jax.checkpoint
        def one(qb, k, v, s0=s0):
            sc = mm("bshd,bthd->bhst", qb, k, mode) / math.sqrt(D)
            qpos = s0 + jnp.arange(qb.shape[1])[:, None]
            sc = jnp.where(qpos >= jnp.arange(S)[None, :], sc, -jnp.inf)
            return mm("bhst,bthd->bshd", jax.nn.softmax(sc, axis=-1), v, mode)

        outs.append(one(qb, k, v))
    return jnp.concatenate(outs, axis=1)


def capacity(T: int, m: dict) -> int:
    """Static per-expert capacity, as the program sizes its dispatch buffer."""
    c = int(T * m["num_experts_per_tok"] * m["moe_capacity_factor"]
            / m["num_local_experts"])
    r = m["moe_capacity_round"] if T >= m["moe_capacity_round"] else 8
    c = max(c, 8)
    return -(-c // r) * r


def experts(lp, x, m, mode):
    """Top-k routed SwiGLU experts over x (T, d), with tokens past each
    expert's capacity dropped in token order; every expert is evaluated on
    every token and masked (plain, not fast)."""
    T = x.shape[0]
    E, k = m["num_local_experts"], m["num_experts_per_tok"]
    probs = jax.nn.softmax(mm("td,de->te", x, lp["router"], "f32"), axis=-1)
    topw, topi = jax.lax.top_k(probs, k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(topi.reshape(-1), E, dtype=jnp.int32)  # (T*k, E)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    keep = (rank < capacity(T, m)).reshape(T, k)
    w = jnp.einsum("tk,tke->te", topw * keep,
                   jax.nn.one_hot(topi, E, dtype=jnp.float32))
    gate = mm("td,edf->etf", x, lp["w_gate"], mode)
    up = mm("td,edf->etf", x, lp["w_up"], mode)
    hid = jax.nn.silu(gate) * up * w.T[:, :, None]
    return mm("etf,efd->td", hid, lp["w_down"], mode)


def block(lp, h, pos, m, mode, q_block):
    """One decoder layer on h (B, S, d), float32 throughout."""
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    B, S, d = h.shape
    H, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    x = rms(h, lp["norm1"], eps)
    a = lp["attn"]
    q = rope(mm("bsd,de->bse", x, a["wq"], mode).reshape(B, S, H, D), pos, theta)
    kk = rope(mm("bsd,de->bse", x, a["wk"], mode).reshape(B, S, Hkv, D), pos, theta)
    vv = mm("bsd,de->bse", x, a["wv"], mode).reshape(B, S, Hkv, D)
    o = attention(q, kk, vv, q_block, mode).reshape(B, S, H * D)
    h = h + mm("bse,ed->bsd", o, a["wo"], mode)
    x = rms(h, lp["norm2"], eps)
    p = lp["mlp"]
    if "router" in p:
        y = experts(p, x.reshape(B * S, d), m, mode).reshape(B, S, d)
    else:
        y = mm("bsf,fd->bsd", jax.nn.silu(mm("bsd,df->bsf", x, p["w_gate"], mode))
               * mm("bsd,df->bsf", x, p["w_up"], mode), p["w_down"], mode)
    return h + y


def hidden(params, tokens, m, mode="f32", q_block=1024):
    """Final normed hidden states (B, S, d) for token ids (B, S)."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = params["embed"][tokens].astype(jnp.float32)
    body = jax.checkpoint(
        lambda h, lp: (block(lp, h, pos, m, mode, min(q_block, S)), None))
    h, _ = jax.lax.scan(body, h, params["blocks"]["b0"])
    return rms(h, params["final_norm"].astype(jnp.float32), m["rms_norm_eps"])


def logits(params, h, mode="f32"):
    return mm("...d,vd->...v", h, params["embed"], mode)


def loss(params, tokens, m, mode="f32"):
    """Mean next-token cross-entropy over positions 0..S-2."""
    lg = logits(params, hidden(params, tokens, m, mode), mode)[:, :-1]
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# ---------------------------------------------------------------------------
# training: AdamW as the job states it
# ---------------------------------------------------------------------------
def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up to ``peak_lr``, then cosine decay to 0 at ``total_steps``."""
    w, T = opt["warmup_steps"], opt["total_steps"]
    if step < w:
        return opt["peak_lr"] * step / max(w, 1)
    prog = min(max((step - w) / max(T - w, 1), 0.0), 1.0)
    return opt["peak_lr"] * 0.5 * (1.0 + math.cos(math.pi * prog))


@functools.partial(jax.jit, static_argnames=("m", "mode", "drop_half"))
def _grad(params, tokens, m, mode, drop_half=False):
    mm_ = dict(m)

    def f(p):
        if not drop_half:
            return loss(p, tokens, mm_, mode)
        # a fault for calibration: the loss of the first half of the tokens
        half = tokens.shape[1] // 2
        lg = logits(p, hidden(p, tokens, mm_, mode), mode)[:, :half]
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, tokens[:, 1:half + 1, None], axis=-1)[..., 0]
        return jnp.mean(lse - gold)

    return jax.value_and_grad(f)(params)


@functools.partial(jax.jit, static_argnames=("stores",), donate_argnums=(0, 1, 2))
def _adam(p32, mu, nu, g, lr, t, opt_vals, stores):
    """One AdamW update of float32 parameters, each then rounded to the
    dtype it is stored in (``stores``, in leaf order)."""
    b1, b2, eps, wd, clip = opt_vals
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    s = jnp.minimum(1.0, clip / (gn + 1e-9))
    tf = t.astype(jnp.float32)
    flat_p, tree = jax.tree.flatten(p32)
    new_p, new_m, new_v = [], [], []
    for p, m, v, g_, store in zip(flat_p, jax.tree.leaves(mu), jax.tree.leaves(nu),
                                  jax.tree.leaves(g), stores):
        g_ = g_ * s
        m = b1 * m + (1 - b1) * g_
        v = b2 * v + (1 - b2) * g_ * g_
        delta = (m / (1 - b1 ** tf)) / (jnp.sqrt(v / (1 - b2 ** tf)) + eps)
        if p.ndim >= 2:  # decoupled decay on every leaf stored with 2+ dims
            delta = delta + wd * p
        new_p.append((p - lr * delta).astype(store).astype(jnp.float32))
        new_m.append(m)
        new_v.append(v)
    clipped = jax.tree.map(lambda x: jnp.linalg.norm((x * s).ravel()), g)
    un = lambda xs: jax.tree.unflatten(tree, xs)
    return un(new_p), un(new_m), un(new_v), clipped


def leaf_norms(tree) -> dict:
    """{path: norm} of every leaf, as Python floats."""
    return {k: float(jnp.linalg.norm(v.astype(jnp.float32).ravel()))
            for k, v in flatten(tree).items()}


def dir_gaps(g, against: dict) -> dict:
    """{path: 1 - cos} between each leaf of ``g`` and the same leaf of
    ``against`` (host arrays), as half the squared distance of the unit
    vectors, which keeps its digits where the two nearly agree."""
    out = {}
    for k, v in flatten(g).items():
        a = v.astype(jnp.float32).ravel()
        b = jnp.asarray(against[k], jnp.float32).ravel()
        u = a / jnp.maximum(jnp.linalg.norm(a), 1e-30) - b / jnp.maximum(jnp.linalg.norm(b), 1e-30)
        out[k] = float(0.5 * jnp.sum(u * u))
    return out


def train(params0, batches, m: dict, opt: dict, mode="f32", drop_half=False,
          against: dict | None = None, keep: bool = False):
    """``len(batches)`` AdamW steps from ``params0`` (stored in its own
    dtype, computed in float32).  Returns the losses, the leaf norms of the
    first gradient after clipping, of the unclipped first gradient, and of
    the parameters' change over all steps.  With ``against`` ({path: host
    array}, another first gradient), also each leaf's ``1 - cos`` against
    it (``grad1_dir``); with ``keep``, the first gradient itself on the host
    (``grad1_vec``)."""
    stores = tuple(str(a.dtype) for a in jax.tree.leaves(params0))
    mt = tuple(sorted(m.items()))
    p32 = jax.tree.map(lambda a: jnp.array(a, jnp.float32, copy=True), params0)
    mu = jax.tree.map(jnp.zeros_like, p32)
    nu = jax.tree.map(jnp.zeros_like, p32)
    vals = tuple(jnp.float32(opt[k]) for k in ("b1", "b2", "eps", "weight_decay",
                                               "clip_norm"))
    out = {"losses": []}
    with jax.default_matmul_precision("highest"):
        for t, toks in enumerate(batches, start=1):
            lval, g = _grad(p32, toks, mt, mode, drop_half)
            out["losses"].append(float(lval))
            if t == 1:
                out["grad1_raw"] = leaf_norms(g)
                if against is not None:
                    out["grad1_dir"] = dir_gaps(g, against)
                if keep:
                    out["grad1_vec"] = {k: np.asarray(v) for k, v in flatten(g).items()}
            p32, mu, nu, clipped = _adam(p32, mu, nu, g, jnp.float32(lr_at(opt, t)),
                                         jnp.int32(t), vals, stores)
            if t == 1:
                out["grad1"] = {k: float(v) for k, v in flatten(clipped).items()}
            del g
    out["change"] = leaf_norms(jax.tree.map(lambda a, b: a - b.astype(jnp.float32),
                                            p32, params0))
    return out


# ---------------------------------------------------------------------------
# inference: logits of whole sequences
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _logits(params, tokens, m, mode):
    return logits(params, hidden(params, tokens, dict(m), mode), mode)


def seq_logits(params, tokens, m: dict, mode="f32"):
    """float32 logits (B, S, V) of every position of ``tokens`` (B, S)."""
    with jax.default_matmul_precision("highest"):
        return _logits(params, tokens, tuple(sorted(m.items())), mode)


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _rel_err(params, tokens, got, m, mode):
    worst = jnp.float32(0)
    for b in range(tokens.shape[0]):  # one row at a time, so it fits
        ref = logits(params, hidden(params, tokens[b:b + 1], dict(m), mode), mode)
        num = jnp.sum(jnp.square(got[b:b + 1].astype(jnp.float32) - ref), axis=-1)
        worst = jnp.maximum(worst, jnp.max(jnp.sqrt(num / jnp.sum(ref * ref, axis=-1))))
    return worst


def logits_rel_err(params, tokens, got, m: dict, mode="f32"):
    """Worst position's ||got - reference|| / ||reference|| over its logits."""
    with jax.default_matmul_precision("highest"):
        return float(_rel_err(params, tokens, got, tuple(sorted(m.items())), mode))


def served_gaps(ref_logits, served):
    """For each position, the reference's best logit minus its logit of the
    token that was served there (0 where the served token is its best)."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, served[..., None], axis=-1)[..., 0]
    return best - got
