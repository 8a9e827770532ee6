"""Share of the traced window in which no op runs on a device: 1 minus
the union of the device's op intervals over the window, averaged over
the devices."""


def read(ctx):
    from harness import xplane
    if ctx.trace is None or not ctx.trace.devices:
        return None
    lo, hi = ctx.window_ps
    idle = [1.0 - xplane.busy(d, lo, hi) / (hi - lo)
            for d in ctx.trace.devices]
    return 100.0 * sum(idle) / len(idle)
