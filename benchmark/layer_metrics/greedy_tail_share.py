"""Share of the paged dispatches of the window whose sampling tail did not
run: the step took the argmax of every row, because no row that emitted asked
to sample. ``paged_steps`` less ``sampled_steps`` over ``paged_steps`` of the
program's serving counters; nothing to read from a program that does not count
``sampled_steps``."""


def read(ctx):
    c = ctx.counters
    if not c.get("paged_steps") or "sampled_steps" not in c:
        return None
    return 100.0 * (c["paged_steps"] - c["sampled_steps"]) / c["paged_steps"]
