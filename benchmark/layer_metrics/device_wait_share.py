"""Share of ``Engine.step`` that the host spends waiting for the device
(the program's ``pt.serve.wait`` spans: from a jitted call's return to its
outputs on the host): ``wait_s`` over ``step_s`` of the program's serving
counters over the window. 100 less it is the host's own share of a boundary,
which ``device_idle.serve`` sees from the device's side."""


def read(ctx):
    c = ctx.counters
    if not ctx.on_chip or not c.get("step_s") or "wait_s" not in c:
        return None
    return 100.0 * c["wait_s"] / c["step_s"]
