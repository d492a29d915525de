"""Share of the bytes of large read outputs that came from a host block
an earlier read had mapped (the program's ``store.read.reused`` counter)
among all of them (``store.read.reused`` and ``store.read.fresh``, a
block mapped for the read), in %."""

from chipbench import obs_table


def read(ctx):
    table = obs_table.table(ctx)
    reused, fresh = (table.get(name, {"n": 0, "bytes": 0})
                     for name in ("store.read.reused", "store.read.fresh"))
    if not reused["n"] + fresh["n"]:
        return None
    return 100.0 * reused["bytes"] / (reused["bytes"] + fresh["bytes"])
