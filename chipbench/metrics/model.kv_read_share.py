"""Share of the decode cache's positions that the decode waves' attention
read in the window: each live slot's length rounded up to the
length-bounded kernel's block, over every slot's full capacity, from the
engine's own counters (``kv_rows_read`` / ``kv_rows_held``). A program
without the counters reports nothing."""


def read(ctx):
    held = ctx.counters.get("kv_rows_held", 0)
    if held <= 0:
        return None
    return 100.0 * ctx.counters.get("kv_rows_read", 0) / held
