"""Share of the CA-server forward and dq grid cells that run a body, over
the window's steps: the plan's counts, carried as the args of each
``train.dispatch`` span (one per step started inside the window)."""


def read(ctx):
    from harness.grid import live_pct
    return live_pct(ctx.spans, "ca_fwd_cells")
