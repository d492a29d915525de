"""Milliseconds spent building the host arrays a read returns (the
program's ``store.assemble`` span: the fill of the output buffer and
each chunk's copy into it) per computed product."""

from chipbench import obs_table


def read(ctx):
    return obs_table.ms_per_product(ctx, "store.assemble")
