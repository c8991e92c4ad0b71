"""Share of a window group's uncapped lifetime that the cache manager maps:
``kv_pages_mapped_window`` (pages mapped in the window groups for the requests
admitted in the window, times the group's layers) over
``kv_pages_unwindowed`` (what those groups would have mapped with no cap: every
page of prompt + output), of the program's serving counters. A ring a slot
keeps it under 100 wherever a context outruns the ring; it reads 100 the day
a change maps a window layer's whole context again. Nothing to read from a
program that does not count them, or that admitted nothing."""


def read(ctx):
    c = ctx.counters
    if not c.get("kv_pages_unwindowed") or not c.get("kv_pages_mapped_window"):
        return None
    return 100.0 * c["kv_pages_mapped_window"] / c["kv_pages_unwindowed"]
