"""Forward model FLOPs of the prompt tokens a hybrid (Mamba-1 + attention)
model prefilled per second, over the chip's bf16 peak: the matrix products
and the attention layers' causal attention (``flops_hybrid.py``; the scan's
elementwise work is not model FLOPs)."""
from chipbench import flops_hybrid


def read(ctx):
    r, pk = ctx["run"], ctx["peaks"]
    rate = r.e2e.get("prefill_tokens_s")
    if not rate:
        return None
    S = r.traffic["seq_len"]
    return 100.0 * rate * flops_hybrid.forward_flops_per_seq(r.model, S) / S / (
        pk["chips"] * pk["bf16_flops_s"])
