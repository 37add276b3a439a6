"""90th percentile of the time to first token, from each request's
scheduled arrival, over every request due in the window (host clock). A
request with no first token when the window closes counts with its wait
so far, so a stall cannot hide."""
import numpy as np


def read(ctx):
    a, b = ctx.window
    waits = []
    for r in ctx.requests:
        due = ctx.t_traffic + r.due
        if not a <= due < b:
            continue
        first = r.stamps[0] if r.stamps and r.stamps[0] <= b else b
        waits.append(first - due)
    return float(np.percentile(waits, 90)) * 1e3 if waits else None
