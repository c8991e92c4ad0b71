"""Share of the paged steps of the window that were prefill chunk steps:
``chunk_steps`` over ``paged_steps`` of the program's serving counters."""


def read(ctx):
    c = ctx.counters
    if not c.get("paged_steps"):
        return None
    return 100.0 * c.get("chunk_steps", 0) / c["paged_steps"]
