"""Output tokens emitted in the window over the window's length (host clock)."""


def read(ctx):
    a, b = ctx.window
    n = sum(1 for r in ctx.requests for t in r.stamps if a <= t < b)
    return n / (b - a)
