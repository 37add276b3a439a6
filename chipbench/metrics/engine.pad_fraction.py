"""Share of the prompt positions the admission programs executed in the
window that were padding (bucket right-pad and power-of-two pad rows),
from the engine's own counters."""


def read(ctx):
    useful = ctx.counters.get("prefill_tokens", 0)
    pad = ctx.counters.get("prefill_pad_tokens", 0)
    if useful + pad <= 0:
        return None
    return 100.0 * pad / (useful + pad)
