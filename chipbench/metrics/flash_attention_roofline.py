"""The ``flash_attention`` Pallas kernel's share of its roofline: the least
time its calls in the window could take at the chip's peaks, over their
device time in the trace.  Each call's work comes from its shapes."""
from chipbench import flops, trace


def read(ctx):
    tr, pk = ctx["trace"], ctx["peaks"]
    calls = (tr or {}).get("kernels", {}).get("flash_attention", [])
    if not calls:
        return None
    least = spent = 0.0
    for text, dur in calls:
        (_, out), (dt, q), (_, k) = trace.arrays(text)[:3]
        B, H, S, D = q
        n, b = flops.flash_attention(B, H, k[1], S, D, trace.nbytes(dt, (1,)))
        least += flops.roofline_s(n, b, pk)
        spent += dur
    return 100.0 * least / spent if spent else None
