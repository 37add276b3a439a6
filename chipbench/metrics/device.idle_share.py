"""Share of the traced window in which no operation ran on the device; on
several chips, the mean of each chip's share."""


def read(ctx):
    red = ctx.trace
    if red is None or red.window_ns <= 0:
        return None
    shares = [100.0 * (1.0 - busy / red.window_ns)
              for busy in red.busy_ns_per_chip()]
    return sum(shares) / len(shares)
