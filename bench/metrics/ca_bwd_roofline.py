"""CA-server backward kernels (``ca_server_bwd``: the dq pass and the
dk/dv pass together): share of their roofline over their summed device
time.  Work per backward: 8 x live pairs x Hq x head_dim FLOPs; q, k, v,
out, dout, lse read and dq, dk, dv written once."""


def read(ctx):
    return ctx.kernel_roofline("pallas_bwd", backward=True, passes=2)
