"""Mean time an admitted request waited in the engine's queue, from
``submit`` to its admission into a slot: ``admit_queue_wait_s`` over
``admit_queue_waits`` of the program's serving counters over the window."""


def read(ctx):
    c = ctx.counters
    if not ctx.on_chip or not c.get("admit_queue_waits"):
        return None
    return 1e3 * c["admit_queue_wait_s"] / c["admit_queue_waits"]
