"""The decode dispatch's share of its bandwidth roofline, over the traced part
of the window: the least bytes that its decode dispatches must read (the
family's ``decode_bytes``: the weights outside the routed experts and the
head once a dispatch, the routed experts that got a token, the latent rows
of the live contexts) over the HBM bandwidth, against the time the device
spent in those dispatches' operations.

A decode dispatch is found by the program's own spans: from the start of a
``pt.serve.feed`` of ``kind=decode`` to the end of the ``pt.serve.wait`` that
follows it the device runs that dispatch's executable and nothing else (the
chunk dispatch before it has its outputs on the host). Its time is the
summed duration of the device's leaf operations that start in between: the
forward and the sampling tail, without the host's feed and turn-around.
The touched experts are the program's counter over the whole window, at the
window's mean a dispatch."""
import bisect

FEED, WAIT, DECODE = "pt.serve.feed#", "pt.serve.wait#", "kind=decode"


def decode_dispatches(trace):
    """[[start_ns, end_ns], ...] of the decode dispatches that lie whole in
    the traced window, by the program's feed and wait spans."""
    feeds = sorted(s for n, s, _ in trace.host
                   if n.startswith(FEED) and DECODE in n)
    out = []
    for n, s, d in sorted(trace.host, key=lambda h: h[1]):
        if n.startswith(WAIT) and DECODE in n:
            i = bisect.bisect_right(feeds, s) - 1
            if i >= 0 and feeds[i] >= trace.window[0] \
                    and s + d <= trace.window[1]:
                out.append([feeds[i], s + d])
    return out


def device_seconds(trace, dev, intervals):
    """Summed duration of the leaf operations that start inside one of the
    (disjoint, sorted) intervals."""
    starts = [a for a, _ in intervals]
    tot = 0
    for _, s, d in trace.leaf_ops(dev):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < intervals[i][1]:
            tot += d
    return tot / 1e9


def read(ctx):
    tr, c = ctx.trace, ctx.counters
    n_window = c.get("paged_steps", 0) - c.get("chunk_steps", 0)
    if tr is None or not ctx.on_chip or n_window <= 0 \
            or "moe_touched_decode" not in c:
        return None
    dispatches = decode_dispatches(tr)
    device_s = device_seconds(tr, tr.used_devices()[0], dispatches)
    if not dispatches or device_s <= 0:
        return None
    n = len(dispatches)
    p = ctx.facts["log"].processed(*ctx.traced)
    nbytes = ctx.work.decode_bytes(
        ctx.config, n, c["moe_touched_decode"] * n / n_window,
        p["decode_ctx_positions"])
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / device_s
