"""Model FLOPs of the decode waves in the traced window (live slots only,
attention over each token's real context) over the device time of those
waves, as a share of the chip's bf16 peak. Only steps that admitted
nothing count, and their device time is every operation in them (the
decode program, its row gathers, argmax and the wave's sync): the
program's decode step is a jitted ``lambda`` today and cannot be told
apart from the gather by name."""
from chipbench import counters
from chipbench.trace import per_step


def read(ctx):
    if ctx.trace is None:
        return None
    flops = t = 0.0
    for step, ns in per_step(ctx.trace, ctx.steps):
        if step.decode_ctx and not step.prefill_lens and ns > 0:
            flops += counters.decode_flops(ctx.config, step.decode_ctx)
            t += ns * 1e-9
    if t <= 0:
        return None
    return 100.0 * flops / t / ctx.peaks["bf16_flops_per_s"]
