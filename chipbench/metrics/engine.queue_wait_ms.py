"""Mean wait of a request admitted in the window, from its submission to
the start of the admission group that took it (waiting for a free slot
and for the wave boundary), from the engine's own counters."""


def read(ctx):
    wait = ctx.counters.get("queue_wait_s_sum")
    n = ctx.counters.get("prefills", 0)
    if wait is None or n <= 0:
        return None
    return wait / n * 1e3
