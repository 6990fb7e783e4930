"""Kernel tile-geometry helpers: pure integer math, NO jax imports.

These working-set formulas are shared by the Pallas kernels (to validate
block choices) and by the analytic cost model (to penalize VMEM-spilling
schedules).  They live in a jax-free module so the search layer — including
``ProTuner``'s process-pool workers, which only ever price schedules —
never drags the XLA runtime into the process.  ``flash_attention`` and
``selective_scan`` re-export them for backward compatibility.
"""
from __future__ import annotations


def flash_vmem_bytes(
    block_q: int, block_kv: int, head_dim: int, dtype_bytes: int = 2
) -> int:
    """Working-set estimate for one flash-attention grid step."""
    io = (block_q + 2 * block_kv + block_q) * head_dim * dtype_bytes
    scratch = (block_q * (2 + head_dim)) * 4
    return io + scratch


def flash_block_runs(qi, kj, q_off: int, block_q: int, block_kv: int):
    """Causal flash attention: whether kv block ``kj`` holds any key that a
    query of q block ``qi`` may see.  Queries sit at positions
    ``q_off + qi*block_q + r`` (the last ``seq_q`` of ``seq_kv``).  Plain
    integer arithmetic, so it takes Python ints and traced program ids."""
    return kj * block_kv <= q_off + (qi + 1) * block_q - 1


def flash_block_full(qi, kj, q_off: int, block_q: int, block_kv: int):
    """Causal flash attention: whether every key of kv block ``kj`` is
    visible to every query of q block ``qi`` (no mask needed)."""
    return kj * block_kv + block_kv - 1 <= q_off + qi * block_q


def flash_last_kv_block(qi, q_off: int, block_q: int, block_kv: int):
    """The last kv block that q block ``qi`` needs under the causal mask."""
    return (q_off + (qi + 1) * block_q - 1) // block_kv


def flash_block_split(
    seq_q: int, seq_kv: int, block_q: int, block_kv: int, causal: bool = True
) -> tuple[int, int, int]:
    """``(full, diagonal, skipped)`` grid steps per (batch, head) of the
    flash-attention forward: full blocks run unmasked, diagonal blocks run
    with the causal mask, skipped blocks run nothing.  Blocks are clipped
    to the sequence as the kernel clips them."""
    block_q, block_kv = min(block_q, seq_q), min(block_kv, seq_kv)
    nq, nkv = seq_q // block_q, seq_kv // block_kv
    if not causal:
        return nq * nkv, 0, 0
    q_off = seq_kv - seq_q
    full = diagonal = 0
    for qi in range(nq):
        for kj in range(nkv):
            if flash_block_full(qi, kj, q_off, block_q, block_kv):
                full += 1
            elif flash_block_runs(qi, kj, q_off, block_q, block_kv):
                diagonal += 1
    return full, diagonal, nq * nkv - full - diagonal


def scan_vmem_bytes(
    chunk: int, d_block: int, n_state: int, dtype_bytes: int = 2
) -> int:
    """Working-set estimate for one selective-scan time chunk."""
    io = (
        3 * chunk * d_block + 2 * chunk * n_state + d_block * n_state + d_block
    ) * dtype_bytes
    scratch = d_block * n_state * 4
    return io + scratch
