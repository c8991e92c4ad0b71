"""Mean host time of one decode dispatch of the paged engine, from its feed
(host arrays, copy-on-write, uploads) to its outputs on the host:
``decode_time_s`` over the decode dispatches (``paged_steps`` less
``chunk_steps``) of the program's serving counters over the window."""


def read(ctx):
    c = ctx.counters
    n = c.get("paged_steps", 0) - c.get("chunk_steps", 0)
    if not ctx.on_chip or n <= 0 or "decode_time_s" not in c:
        return None
    return 1e3 * c["decode_time_s"] / n
