"""Milliseconds of multi-chunk store reads per computed product: the
program's ``store.read`` span, each read's wall time on the thread that
asked for it (its chunks may be decoded and copied on the read pool's
threads meanwhile)."""

from chipbench import obs_table


def read(ctx):
    return obs_table.ms_per_product(ctx, "store.read")
