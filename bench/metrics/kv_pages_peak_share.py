"""Page cache: the most pages allocated at once in the window, as a share
of the pool (counters)."""


def read(ctx):
    return 100.0 * ctx.counters["peak_pages"] / ctx.pool_pages
