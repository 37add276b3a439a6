"""Model FLOPs of the prompt tokens admitted in the traced window (useful
tokens only) over the admission programs' device time, as a share of the
chip's bf16 peak."""
from chipbench import counters
from chipbench.trace import per_step

ADMIT = r"jit__admit_wave_fn"


def read(ctx):
    if ctx.trace is None:
        return None
    flops = t = 0.0
    for step, ns in per_step(ctx.trace, ctx.steps, ADMIT):
        if step.prefill_lens and ns > 0:
            flops += counters.prefill_flops(ctx.config, step.prefill_lens)
            t += ns * 1e-9
    if t <= 0:
        return None
    return 100.0 * flops / t / ctx.peaks["bf16_flops_per_s"]
