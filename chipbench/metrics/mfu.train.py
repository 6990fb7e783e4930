"""Model FLOPs of the training tokens completed per second (forward and
backward, no recomputation, causal attention included) over the chips'
bf16 peak."""
from chipbench import flops


def read(ctx):
    r, pk = ctx["run"], ctx["peaks"]
    rate = r.e2e.get("train_tokens_s")
    if not rate:
        return None
    per_token = flops.train_flops_per_token(r.model, r.traffic["seq_len"])
    return 100.0 * rate * per_token / (pk["chips"] * pk["bf16_flops_s"])
