"""The ``moe_gemm`` Pallas kernel's share of its roofline: the least time
its calls in the window could take at the chip's peaks, over their device
time in the trace.  Each call's work comes from its shapes in the trace."""
from chipbench import flops, trace


def read(ctx):
    tr, pk = ctx["trace"], ctx["peaks"]
    calls = (tr or {}).get("kernels", {}).get("moe_gemm", [])
    if not calls:
        return None
    least = spent = 0.0
    for text, dur in calls:
        (_, out), (dt, x), (_, w) = trace.arrays(text)[:3]
        n, b = flops.moe_gemm(*x, w[2], trace.nbytes(dt, (1,)))
        least += flops.roofline_s(n, b, pk)
        spent += dur
    return 100.0 * least / spent if spent else None
