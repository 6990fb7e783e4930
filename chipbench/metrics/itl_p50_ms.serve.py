"""Median gap between consecutive output tokens of one request, over all
such gaps in the window (host clock)."""
import numpy as np


def read(ctx):
    gaps = ctx["run"].counts.get("itl_ms")
    return float(np.median(gaps)) if gaps else None
