"""Mean milliseconds a boundary that ``Engine.step`` spends in its feed phase
(the program's ``pt.serve.feed`` span: building the host arrays, copy-on-
write, the uploads and the jitted call, until it has returned its futures
(all dispatches of a boundary together)): ``feed_s`` over ``boundaries`` of
the program's serving counters over the window."""


def read(ctx):
    c = ctx.counters
    if not ctx.on_chip or not c.get("boundaries") or "feed_s" not in c:
        return None
    return 1e3 * c["feed_s"] / c["boundaries"]
