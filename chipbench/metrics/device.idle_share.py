"""Share of the traced window in which no operation ran on the device
(one minus the union of device-operation intervals), in %."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0.0:
        return None
    return 100.0 * (1.0 - ctx.trace.mean_busy_s / ctx.trace.window_s)
