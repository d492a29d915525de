"""Store chunks fetched and decoded per computed product: the delta of
``Session.cache_stats()["chunk_fetches"]`` over every session opened,
over ``compute_product`` calls in the window."""


def read(ctx):
    if not ctx.computed:
        return None
    return ctx.chunk_fetches / ctx.computed
