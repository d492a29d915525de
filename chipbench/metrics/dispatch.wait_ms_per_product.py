"""Milliseconds product code waited for a kernel's result to reach the
host (the program's ``dispatch.wait`` span: copies to the device still in
flight, the kernel, the copy back) per computed product."""

from chipbench import obs_table


def read(ctx):
    return obs_table.ms_per_product(ctx, "dispatch.wait")
