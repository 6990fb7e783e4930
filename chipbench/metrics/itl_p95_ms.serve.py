"""95th percentile of the gaps between consecutive output tokens of one
request, over all such gaps in the window (host clock; a gap ends when the
token is on the host)."""
import numpy as np


def read(ctx):
    gaps = ctx["run"].counts.get("itl_ms")
    return float(np.percentile(gaps, 95)) if gaps else None
