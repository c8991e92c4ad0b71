"""Mean milliseconds a boundary that ``Engine.step`` spends in its emit phase
(the program's ``pt.serve.emit`` span: from the tokens on the host to the end
of the boundary's bookkeeping: key copy, token emission, the clients'
on_token, resolution): ``emit_s`` over ``boundaries`` of the program's
serving counters over the window."""


def read(ctx):
    c = ctx.counters
    if not ctx.on_chip or not c.get("boundaries") or "emit_s" not in c:
        return None
    return 1e3 * c["emit_s"] / c["boundaries"]
