"""Share of the summed client latency spent outside
``ArchiveService.compute_product`` (queueing, HTTP, cache, encoding), in
%: one minus the harness-timed compute seconds over the latency seconds
of the answered requests."""


def read(ctx):
    latency = sum(r["latency_s"] for r in ctx.requests if r["status"] == 200)
    if latency <= 0.0:
        return None
    return 100.0 * (1.0 - ctx.compute_s / latency)
