"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode on CPU executes the exact kernel bodies)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gemm import moe_gemm
from repro.kernels.quantize import dequantize_int8, quantize_int8
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.selective_scan import selective_scan

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "B,Hq,Hkv,S,D,bq,bkv,causal",
    [
        (2, 4, 2, 256, 64, 128, 128, True),
        (1, 8, 8, 128, 32, 64, 64, True),     # MHA
        (2, 4, 1, 256, 64, 128, 64, True),    # MQA, asymmetric blocks
        (1, 4, 2, 256, 128, 256, 128, True),  # block_q == S
        (2, 4, 2, 128, 64, 128, 128, False),  # non-causal
        (1, 2, 2, 512, 64, 128, 256, True),   # bkv > bq
    ],
)
def test_flash_attention_matches_ref(B, Hq, Hkv, S, D, bq, bkv, causal):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hq, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_kv=bkv, interpret=True)
    exp = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2, 128, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2, 128, 64), jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
    exp = ref.attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), **_tol(jnp.bfloat16)
    )


# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "B,L,Di,N,chunk,dblk",
    [
        (2, 64, 32, 8, 16, 16),
        (1, 128, 64, 16, 64, 32),
        (2, 32, 16, 4, 32, 16),   # chunk == L
        (1, 96, 48, 8, 32, 48),   # dblk == Di
    ],
)
def test_selective_scan_matches_ref(B, L, Di, N, chunk, dblk):
    ks = jax.random.split(KEY, 5)
    u = jax.random.normal(ks[0], (B, L, Di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, Di)))
    A = -jnp.exp(jax.random.normal(ks[2], (Di, N)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, L, N))
    Cm = jax.random.normal(ks[4], (B, L, N))
    D = jnp.linspace(0.1, 1.0, Di)
    out = selective_scan(u, dt, A, Bm, Cm, D, chunk=chunk, d_block=dblk, interpret=True)
    exp = ref.selective_scan(u, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4, rtol=1e-3)


def test_selective_scan_step_consistency():
    """Decode step replays the full scan one token at a time."""
    B, L, Di, N = 2, 16, 8, 4
    ks = jax.random.split(KEY, 5)
    u = jax.random.normal(ks[0], (B, L, Di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, Di)))
    A = -jnp.exp(jax.random.normal(ks[2], (Di, N)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, L, N))
    Cm = jax.random.normal(ks[4], (B, L, N))
    D = jnp.ones(Di) * 0.3
    full = ref.selective_scan(u, dt, A, Bm, Cm, D)
    x = jnp.zeros((B, Di, N))
    ys = []
    for t in range(L):
        x, y = ref.selective_scan_step(x, u[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D)
        ys.append(y)
    step_out = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(step_out), np.asarray(full), atol=1e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape,block", [((3, 7, 64), 4), ((16, 128), 16), ((5, 96), 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, block, dtype):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], shape, dtype)
    w = jax.random.normal(ks[1], (shape[-1],), dtype)
    out = rmsnorm(x, w, block_rows=block, interpret=True)
    exp = ref.rmsnorm(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), **_tol(dtype)
    )


# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "E,C,d,f,bc,bf,bd",
    [(4, 32, 64, 48, 16, 16, 32), (2, 16, 32, 32, 16, 32, 16), (8, 8, 16, 16, 8, 16, 16)],
)
def test_moe_gemm_matches_ref(E, C, d, f, bc, bf, bd):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (E, C, d))
    w = jax.random.normal(ks[1], (E, d, f))
    out = moe_gemm(x, w, block_c=bc, block_f=bf, block_d=bd, interpret=True)
    exp = ref.moe_gemm(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("R,C", [(8, 128), (16, 64), (4, 256)])
def test_quantize_roundtrip(R, C):
    x = jax.random.normal(KEY, (R, C)) * 3.0
    q, s = quantize_int8(x, block_rows=4, interpret=True)
    qr, sr = ref.quantize_int8(x)
    assert (np.asarray(q) == np.asarray(qr)).all()
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    xd = dequantize_int8(q, s, interpret=True)
    # error bounded by scale/2 per element
    err = np.abs(np.asarray(xd) - np.asarray(x))
    bound = np.asarray(s) * 0.5 + 1e-7
    assert (err <= bound).all()


# ---------------------------------------------------------------------------
# row-group scan kernel at chunk / d_block values the sweep above does not use
@pytest.mark.parametrize(
    "B,L,Di,N,chunk,dblk,dtype",
    [
        (1, 48, 40, 8, 24, 40, jnp.float32),    # chunk of 3 row groups, odd Di
        (2, 80, 64, 16, 40, 16, jnp.float32),   # 2 chunks of 5 groups, N=16
        (1, 64, 96, 16, 64, 32, jnp.bfloat16),  # bf16 blocks, as the models run
        (1, 8, 24, 4, 8, 8, jnp.float32),       # one group per chunk
    ],
)
def test_selective_scan_row_groups_match_ref(B, L, Di, N, chunk, dblk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    u = jax.random.normal(ks[0], (B, L, Di)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, Di))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (Di, N)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, L, N)).astype(dtype)
    Cm = jax.random.normal(ks[4], (B, L, N)).astype(dtype)
    D = jnp.linspace(0.1, 1.0, Di)
    out = selective_scan(u, dt, A, Bm, Cm, D, chunk=chunk, d_block=dblk, interpret=True)
    exp = ref.selective_scan(u, dt, A, Bm, Cm, D)
    tol = _tol(dtype) if dtype == jnp.bfloat16 else dict(atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), **tol
    )


def test_selective_scan_at_the_hybrid_cell_block_shapes():
    """The hybrid prefill cell's kernel call (jamba2-3b: d_inner 5,120, N 16)
    in the default 256-wide d_inner blocks and the scan_chunk of the cell's
    tuned plan, over two chunks so that the state carries between them."""
    from repro.core.autotuner import autotune
    from repro.kernels.ops import DEFAULT_TILES

    chunk = autotune("jamba2-3b", "prefill_32k", algo="mcts_1s", seed=0).plan.scan_chunk
    B, L, Di, N = 1, 2 * chunk, 5120, 16
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    u = jax.random.normal(ks[0], (B, L, Di)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, Di)) - 3.0).astype(jnp.bfloat16)
    A = -jnp.broadcast_to(jnp.arange(1.0, N + 1), (Di, N))
    Bm = jax.random.normal(ks[3], (B, L, N)).astype(jnp.bfloat16)
    Cm = jax.random.normal(ks[4], (B, L, N)).astype(jnp.bfloat16)
    D = jnp.ones((Di,))
    out = selective_scan(u, dt, A, Bm, Cm, D, chunk=chunk, d_block=DEFAULT_TILES.scan_d_block,
                         interpret=True)
    exp = ref.selective_scan(u, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), **_tol(jnp.bfloat16)
    )


def test_selective_scan_rejects_unaligned_chunk():
    u = jnp.zeros((1, 12, 8))
    with pytest.raises(AssertionError):
        selective_scan(u, u, jnp.zeros((8, 4)), jnp.zeros((1, 12, 4)),
                       jnp.zeros((1, 12, 4)), jnp.zeros(8), chunk=12,
                       d_block=8, interpret=True)


# ---------------------------------------------------------------------------
# custom_vjp: Pallas forward, oracle backward
def _grads(fn, args, cot):
    return jax.vjp(fn, *args)[1](cot)


def _kernel_cases():
    from repro.kernels.ops import KernelTiles

    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    tiles = KernelTiles(attn_block_q=64, attn_block_kv=64, scan_chunk=16,
                        scan_d_block=16, moe_block_c=16, moe_block_f=16,
                        moe_block_d=16)
    q = jax.random.normal(ks[0], (1, 4, 128, 32))
    k = jax.random.normal(ks[1], (1, 2, 128, 32))
    v = jax.random.normal(ks[2], (1, 2, 128, 32))
    u = jax.random.normal(ks[3], (1, 32, 32))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (1, 32, 32)))
    A = -jnp.exp(jax.random.normal(ks[5], (32, 8)) * 0.5)
    Bm = jax.random.normal(ks[6], (1, 32, 8))
    Cm = jax.random.normal(ks[7], (1, 32, 8))
    D = jnp.linspace(0.1, 1.0, 32)
    x = jax.random.normal(ks[0], (4, 16, 32))
    w = jax.random.normal(ks[1], (4, 32, 48))
    h = jax.random.normal(ks[2], (3, 5, 64))
    g = jax.random.normal(ks[3], (64,))
    return {
        "attention": (lambda o: lambda *a: o.attention(*a, tiles=tiles),
                      ref.attention, (q, k, v)),
        "selective_scan": (lambda o: lambda *a: o.selective_scan(*a, tiles=tiles),
                           ref.selective_scan, (u, dt, A, Bm, Cm, D)),
        "rmsnorm": (lambda o: o.rmsnorm, ref.rmsnorm, (h, g)),
        "moe_gemm": (lambda o: lambda *a: o.moe_gemm(*a, tiles=tiles),
                     ref.moe_gemm, (x, w)),
    }


@pytest.mark.parametrize("name", ["attention", "selective_scan", "rmsnorm", "moe_gemm"])
def test_custom_vjp_matches_oracle_grad(name):
    from repro.kernels import ops

    make, oracle, args = _kernel_cases()[name]
    fn = make(ops)
    cot = jax.random.normal(jax.random.PRNGKey(11), oracle(*args).shape)
    with ops.kernel_mode("interpret"):
        out = jax.jit(fn)(*args)
        got = jax.jit(lambda *a: _grads(fn, a, cot))(*args)
        jaxpr = str(jax.make_jaxpr(lambda *a: _grads(fn, a, cot))(*args))
    assert "pallas_call" in jaxpr  # the forward really ran the kernel
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle(*args)),
                               atol=2e-4, rtol=2e-4)
    want = _grads(oracle, args, cot)
    assert len(got) == len(want) == len(args)
    for a, gk, gr in zip(args, got, want):
        assert gk.shape == a.shape and gk.dtype == a.dtype
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=2e-4, rtol=2e-4)
