"""Mean milliseconds a boundary that ``Engine.step`` spends inside the
jitted calls of its dispatches (the program's ``pt.serve.launch`` spans,
nested in ``pt.serve.feed``: argument handling over the parameter tree and
the launch, which packing less cannot shorten): ``launch_s`` over
``boundaries`` of the program's serving counters over the window. The part
of ``host_feed_ms`` that is the call itself."""


def read(ctx):
    c = ctx.counters
    if not ctx.on_chip or not c.get("boundaries") or "launch_s" not in c:
        return None
    return 1e3 * c["launch_s"] / c["boundaries"]
