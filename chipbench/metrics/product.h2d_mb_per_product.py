"""Megabytes of host arrays handed to the kernels (each becomes a
host-to-device copy) per computed product."""


def read(ctx):
    if not ctx.computed:
        return None
    return ctx.h2d_bytes / 1e6 / ctx.computed
