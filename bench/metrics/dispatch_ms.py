"""Median host time the trainer takes to hand a step to the device: the
batch's transfer and the jitted call's launch (``train.dispatch`` spans
of the program's ``repro.obs`` recorder) started inside the window."""
import statistics


def read(ctx):
    d = [s.dur for s in ctx.spans if s.name == "train.dispatch"]
    return 1000.0 * statistics.median(d) if d else None
