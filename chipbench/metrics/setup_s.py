"""Process start to the opening of the measured window (host clock):
weights, engine, warm-up of every shape and the traffic's lead-in."""


def read(ctx):
    return ctx.setup_s
