"""Milliseconds of object-store GETs of chunk payloads (the program's
``store.get`` span) per computed product."""

from chipbench import obs_table


def read(ctx):
    return obs_table.ms_per_product(ctx, "store.get")
