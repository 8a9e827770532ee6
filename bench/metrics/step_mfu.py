"""Whole step's share of the chips' bf16 peak: the FLOPs the forward and
backward passes of the window's batches need (``harness.flops``:
backward = 2 x forward, no recomputation, attention over live pairs),
over the window's time, chips and peak."""


def read(ctx):
    from harness import flops
    need = sum(flops.step_flops(ctx.config, b) for b in ctx.batches)
    return 100.0 * need / (ctx.window_s * ctx.chips
                           * ctx.peaks["bf16_flops"])
