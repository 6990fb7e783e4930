"""The ``selective_scan`` Pallas kernel's share of its roofline: the least
time its calls in the window could take at the chip's peaks, over their
device time in the trace.  Each call's work comes from its shapes: the
result (B, L, Di), then u, dt, A transposed (N, Di), B, C and D."""
from chipbench import flops, flops_hybrid, trace


def read(ctx):
    tr, pk = ctx["trace"], ctx["peaks"]
    calls = (tr or {}).get("kernels", {}).get("selective_scan", [])
    if not calls:
        return None
    least = spent = 0.0
    for text, dur in calls:
        (dt, (B, L, Di)), _, _, (_, (N, _)) = trace.arrays(text)[:4]
        n, b = flops_hybrid.selective_scan(B, L, Di, N, trace.nbytes(dt, (1,)))
        least += flops.roofline_s(n, b, pk)
        spent += dur
    return 100.0 * least / spent if spent else None
