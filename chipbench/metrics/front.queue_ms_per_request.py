"""Milliseconds a request in the window waited for one of the server's
workers (the program's ``http.queue`` counter), per request."""

from chipbench import obs_table


def read(ctx):
    row = obs_table.table(ctx).get("http.queue")
    if row is None or not row["n"]:
        return None
    return 1e3 * row["s"] / row["n"]
