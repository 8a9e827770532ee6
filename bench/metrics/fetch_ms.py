"""Median host time the trainer waits for its next planned batch
(``train.fetch`` spans of the program's ``repro.obs`` recorder) started
inside the window."""
import statistics


def read(ctx):
    d = [s.dur for s in ctx.spans if s.name == "train.fetch"]
    return 1000.0 * statistics.median(d) if d else None
