"""Whole step's share of the chips' bf16 peak: the FLOPs the forward and
backward passes of the window's batches need (the reference module's
``step_flops``: backward = 2 x forward, no recomputation, attention over
live pairs), over the window's time, chips and peak."""


def read(ctx):
    need = sum(ctx.ref.step_flops(ctx.config, b) for b in ctx.batches)
    return 100.0 * need / (ctx.window_s * ctx.chips
                           * ctx.peaks["bf16_flops"])
