"""Milliseconds of kernel calls from product code (the program's
``dispatch.call`` span: argument conversion, staging the host arrays for
their copy to the device, and the enqueue) per computed product."""

from chipbench import obs_table


def read(ctx):
    return obs_table.ms_per_product(ctx, "dispatch.call")
