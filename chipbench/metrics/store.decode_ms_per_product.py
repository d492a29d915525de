"""Milliseconds of chunk decoding by the codec (the program's
``store.decode`` span, one per chunk fetched) per computed product."""

from chipbench import obs_table


def read(ctx):
    return obs_table.ms_per_product(ctx, "store.decode")
