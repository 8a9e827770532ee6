"""Median host time of one plan (``plan.build`` spans of the program's
``repro.obs`` recorder) started inside the window."""
import statistics


def read(ctx):
    d = [s.dur for s in ctx.spans if s.name == "plan.build"]
    return 1000.0 * statistics.median(d) if d else None
