"""Host-to-device arrays a paged dispatch (decode, chunk) of the window sent:
``paged_uploads`` over ``paged_steps`` of the program's serving counters,
counted by the program where it sends them. 1.0 where a dispatch's slot
operands travel as one buffer; nine to eleven where each travels alone.
Nothing to read on an empty window or from a program that does not count
``paged_uploads``."""


def read(ctx):
    c = ctx.counters
    if not c.get("paged_steps") or "paged_uploads" not in c:
        return None
    return c["paged_uploads"] / c["paged_steps"]
