"""Forward model FLOPs of the prompt tokens prefilled per second (causal
attention included) over the chip's bf16 peak."""
from chipbench import flops


def read(ctx):
    r, pk = ctx["run"], ctx["peaks"]
    rate = r.e2e.get("prefill_tokens_s")
    if not rate:
        return None
    S = r.traffic["seq_len"]
    return 100.0 * rate * flops.forward_flops_per_seq(r.model, S) / S / (
        pk["chips"] * pk["bf16_flops_s"])
