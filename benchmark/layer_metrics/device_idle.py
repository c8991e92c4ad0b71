"""Share of the traced window in which no operation ran on the device; on
several chips the idlest device."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not ctx.on_chip or not tr.used_devices():
        return None
    return 100.0 * max(tr.idle_share(d) for d in tr.used_devices())
