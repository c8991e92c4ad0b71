"""Share of an all-paged cache that the cache manager binds for a model
whose layers mostly keep a state a slot and no rows a token:
``cache_bytes_bound`` (for the requests admitted in the window: the pages
mapped, times their group's layers and a page's bytes at the model's own row
width, plus the state groups' bytes of one slot) over
``cache_bytes_all_paged`` (what the same lifetimes would have mapped had
every state layer kept rows a token as the paged layers do), of the program's
serving counters. Lower is better: it is what lets a chip hold more
conversations at once; it reads 100 the day a state layer keeps its whole
context again. Nothing to read from a program that does not count them, or
that admitted nothing."""


def read(ctx):
    c = ctx.counters
    if not c.get("cache_bytes_all_paged") or not c.get("cache_bytes_bound"):
        return None
    return 100.0 * c["cache_bytes_bound"] / c["cache_bytes_all_paged"]
