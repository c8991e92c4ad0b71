"""Mean host time of one prefill chunk dispatch, from its feed to its
outputs on the host: ``prefill_time_s`` over ``chunk_steps`` of the program's
serving counters over the window."""


def read(ctx):
    c = ctx.counters
    if not ctx.on_chip or not c.get("chunk_steps") \
            or "prefill_time_s" not in c:
        return None
    return 1e3 * c["prefill_time_s"] / c["chunk_steps"]
