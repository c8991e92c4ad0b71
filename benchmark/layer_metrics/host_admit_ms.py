"""Mean milliseconds a boundary that ``Engine.step`` spends in its admit phase
(the program's ``pt.serve.admit`` span: from the top of the step to the first
dispatch: fault hooks, eviction, expiry, shedding, transfers, the scheduler's
admission and the slot binding): ``admit_s`` over ``boundaries`` of the
program's serving counters over the window."""


def read(ctx):
    c = ctx.counters
    if not ctx.on_chip or not c.get("boundaries") or "admit_s" not in c:
        return None
    return 1e3 * c["admit_s"] / c["boundaries"]
