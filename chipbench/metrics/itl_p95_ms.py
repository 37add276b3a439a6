"""95th percentile of the gap between consecutive tokens of a request,
over every gap that ends in the window (host clock)."""
import numpy as np


def read(ctx):
    a, b = ctx.window
    gaps = [t1 - t0 for r in ctx.requests
            for t0, t1 in zip(r.stamps, r.stamps[1:]) if a <= t1 < b]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
