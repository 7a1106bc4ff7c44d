"""Model FLOP/s utilisation of the traced cycles, in % of the chips' peak:
the model FLOPs of every local step in them (``harness.yardstick``) over
the traced window's seconds x chips x the bf16 peak of the device kind."""


def read(ctx):
    if ctx.steps == 0:
        return None
    flops = ctx.step_flops * ctx.steps
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.chips
                            * ctx.peaks["bf16_flops_per_s"])
