"""Programs compiled or loaded from the persistent cache inside the
window (JAX monitoring events): work that set-up should have done."""


def read(ctx):
    return float(ctx.compiles)
