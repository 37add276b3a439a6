"""Mean device time of one decode step program (``jit_decode_step``, the
program's named decode step) in the traced window. Programs cut by the
window's edges are left out."""
DECODE = r"^jit_decode_step\b"


def read(ctx):
    red = ctx.trace
    if red is None:
        return None
    lo, hi = red.window
    ns = [p.end - p.start for p in red.programs_matching(DECODE)
          if lo < p.start and p.end < hi]
    if not ns:
        return None
    return sum(ns) / len(ns) * 1e-6
