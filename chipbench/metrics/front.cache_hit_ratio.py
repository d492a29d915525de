"""Share of product requests in the window answered from the product
cache (``ArchiveService.stats()`` hits over ``product`` calls), in %."""


def read(ctx):
    if not ctx.products:
        return None
    return 100.0 * ctx.cache_hits / ctx.products
