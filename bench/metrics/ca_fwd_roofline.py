"""CA-server forward kernel (``ca_server_fwd``, with its rematerialised
calls): share of its roofline over its summed device time.  Work per
call: 4 x live pairs x Hq x head_dim FLOPs; q, k, v read and out, lse
written once."""


def read(ctx):
    return ctx.kernel_roofline("pallas_fwd", backward=False, passes=1)
