"""``qvp_reduce``'s share of its roofline, in %: the least time its calls
need (per call the larger of operations over peak FLOP/s and bytes over
HBM bandwidth, from ``chipbench/cost/qvp_reduce.py``) over the device time
of its jitted program in the trace."""


def read(ctx):
    return ctx.roofline("qvp_reduce")
