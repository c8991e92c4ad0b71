"""The device's idle time a paged dispatch that the host spent LAUNCHING
it: the idle in the traced window (the gaps of 2 us and more in the union of
the device's operations, found as ``Trace.idle_gaps`` and ``device_idle``
find them) that lies under a ``pt.serve.feed`` span (the host packs, uploads
and makes the jitted call), or under a ``pt.serve.wait`` span that began
inside the gap (the call has returned, the executable has not started), over
the runs of the program's paged executables whole inside the window.

``idle_split`` is the one split of the idle; ``idle_fetch_ms`` and
``idle_turn_ms`` read its other two parts over the same count. The three
sum to the idle of ``device_idle`` less the seams under 2 us (between
operations of one executable: not the host's).

The two clocks. On a v5e profile the device plane lies EARLY against the
host planes by 1.6-1.9 ms (a run is shown starting before the runtime
enqueued it; ``Trace`` keeps no runtime event to align them by), which is
most of a dispatch's idle: read as it stands, every gap would lie under
the wait of the dispatch that FOLLOWS it. A dispatch bounds the shift from
both sides: its run cannot start before its ``pt.serve.launch`` span has
ended (the runtime enqueues the program as the jitted call returns: its
``DoEnqueueProgram`` lies within 30 us of that end in the profiles looked
at), nor end after its ``pt.serve.wait`` span has (the outputs are on the
host). ``clock_shift`` takes the middle of what every dispatch of the
window allows, and the split is made on the device plane so shifted. What
that leaves uncertain is half the room, 0.4-0.5 ms between launch and
fetch (their sum and the turn are not touched); against the runtime's own
``CompleteCallbacks`` events, which the program's ``profiler.device_time``
aligns by, it read within 0.05 ms (PERF.md).

Nothing to read off the chip, without a trace, or from a program whose spans
carry no ``exe=``."""
import bisect

from benchmark.layer_metrics.decode_device_ms import (
    FEED, executables, runs, span_tags)

WAIT, LAUNCH = "pt.serve.wait#", "pt.serve.launch#"
SEAM_NS = 2000


def dispatches(trace):
    """``[[launch end, wait end, exe], ...]`` by the program's spans, in
    time order: a launch span and the next wait span of the same
    executable."""
    spans = sorted((s, s + d, n) for n, s, d in trace.host
                   if n.startswith((LAUNCH, WAIT)))
    out, open_launch = [], {}
    for s, e, n in spans:
        exe = span_tags(n).get("exe")
        if n.startswith(LAUNCH):
            open_launch[exe] = e
        elif exe in open_launch:
            out.append([open_launch.pop(exe), e, exe])
    return out


def clock_shift(trace, dev):
    """Nanoseconds to ADD to the device plane's times: the middle of the
    room that the window's dispatches leave (a run between its dispatch's
    launch end and wait end), 0 where no run meets a dispatch."""
    found = dispatches(trace)
    starts = [d[0] for d in found]
    room = []               # (least, most) shift a matched run allows
    for exe in {d[2] for d in found}:
        for s, dur in runs(trace, dev, [exe]):
            # the dispatch of this executable that the run overlaps most
            i = bisect.bisect_right(starts, s + dur)
            over, span = max(
                ((min(w1, s + dur) - max(l1, s), (l1, w1))
                 for l1, w1, name in found[max(0, i - 4):i] if name == exe),
                default=(0, None))
            if over > 0:
                room.append((span[0] - s, span[1] - s - dur))
    if not room:
        return 0
    return (max(r[0] for r in room) + min(r[1] for r in room)) // 2


def _overlap(a, b, s, e):
    return max(0, min(b, e) - max(a, s))


def idle_split(trace, dev):
    """``{"launch", "fetch", "turn"}`` in seconds: the device's idle gaps of
    the window, the device plane shifted by ``clock_shift``, by what the
    program's host loop was doing under them. ``fetch`` is what lies under
    a ``pt.serve.wait`` span that began before the gap did (the executable
    has ended, the host does not hold its outputs yet); ``turn`` the rest
    (emit, admit, the boundary's remainder, between steps)."""
    shift = clock_shift(trace, dev)
    busy = trace._union([[t, s + shift, d]
                         for t, s, d in trace.devices[dev]["ops"]])
    gaps = [g for g in trace._minus([list(trace.window)], busy)
            if g[1] - g[0] >= SEAM_NS]
    # the phases of one host loop are disjoint: sorted by start is sorted
    # by end
    spans = sorted((s, s + d, n.startswith(WAIT)) for n, s, d in trace.host
                   if n.startswith((FEED, WAIT)))
    ends = [e for _, e, _ in spans]
    launch = fetch = total = 0
    for a, b in gaps:
        total += b - a
        i = bisect.bisect_right(ends, a)
        while i < len(spans) and spans[i][0] < b:
            s, e, is_wait = spans[i]
            if is_wait and s < a:
                fetch += _overlap(a, b, s, e)
            else:
                launch += _overlap(a, b, s, e)
            i += 1
    return {"launch": launch / 1e9, "fetch": fetch / 1e9,
            "turn": (total - launch - fetch) / 1e9}


def idle_ms(ctx, part):
    """Milliseconds of idle of ``part`` a paged executable run, or None."""
    tr = ctx.trace
    if tr is None or not ctx.on_chip or not tr.used_devices():
        return None
    dev = max(tr.used_devices(), key=tr.idle_share)
    n = len(runs(tr, dev, executables(tr)))
    if n == 0:
        return None
    return 1e3 * idle_split(tr, dev)[part] / n


def read(ctx):
    return idle_ms(ctx, "launch")
