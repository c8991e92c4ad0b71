"""Median wall time of the benchmark's own span around each Engine.step call
in the measured window (host clock)."""
import statistics


def read(ctx):
    if not ctx.on_chip:
        return None
    log = ctx.facts["log"]
    d = ctx.spans.durations("Engine.step", log.t0, log.t_close)
    return 1e3 * statistics.median(d) if d else None
