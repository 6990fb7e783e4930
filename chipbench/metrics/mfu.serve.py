"""Forward model FLOPs of the useful tokens of the window (prompt tokens
fed and tokens served, each attending to its own context) per second, over
the chip's bf16 peak."""
from chipbench import flops


def read(ctx):
    r, pk = ctx["run"], ctx["peaks"]
    c = r.counts
    if not r.window_s or "served_contexts" not in c:
        return None
    contexts = c["fed_contexts"] + c["served_contexts"]
    work = 2 * flops.matmul_params(r.model) * len(contexts) + sum(
        flops.attention_flops(r.model, n) for n in contexts)
    return 100.0 * work / r.window_s / (pk["chips"] * pk["bf16_flops_s"])
