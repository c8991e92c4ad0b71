"""Share of the held routed experts that a decode dispatch's tokens reach:
``moe_touched_decode`` (held experts that got at least one token, summed over
expert layers and decode dispatches) over the held experts times
``moe_layer_dispatches_decode``, of the program's serving counters over the
window. What a decode step that reads only the touched experts would leave
unread is 100 less this."""


def read(ctx):
    c = ctx.counters
    layers = c.get("moe_layer_dispatches_decode", 0)
    if not ctx.on_chip or layers <= 0 or "moe_touched_decode" not in c:
        return None
    lo, hi = ctx.config.get("experts_held") or \
        (0, ctx.config["n_routed_experts"])
    return 100.0 * c["moe_touched_decode"] / ((hi - lo) * layers)
