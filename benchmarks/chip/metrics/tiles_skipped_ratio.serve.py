"""Share of the pruned stream's (user block, tile) visits skipped, summed
over the window's transactions (the program's ``RetrievalMetrics``)."""


def read(ctx):
    total = ctx.counters.get("tiles_total", 0)
    if not total:
        return None
    return ctx.counters["tiles_skipped"] / total
