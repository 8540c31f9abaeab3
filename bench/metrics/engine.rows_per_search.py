"""engine.rows_per_search: real (unpadded) query rows per device search
over the window, from RetrievalStats.searched_queries / searches."""


def read(ctx):
    if not ctx.stats.get("searches"):
        return None
    return ctx.stats["searched_queries"] / ctx.stats["searches"]
