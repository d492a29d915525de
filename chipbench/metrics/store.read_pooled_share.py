"""Share of a multi-chunk read's chunks that a helper on the read pool
took (the program's ``store.read.pooled`` count) among all of them
(``store.read.pooled`` and ``store.read.inline``, those the reading
thread took itself), in %."""

from chipbench import obs_table


def read(ctx):
    table = obs_table.table(ctx)
    pooled, inline = (table.get(name, {"n": 0})["n"]
                      for name in ("store.read.pooled", "store.read.inline"))
    if not pooled + inline:
        return None
    return 100.0 * pooled / (pooled + inline)
