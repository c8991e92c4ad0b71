"""Device time by the program's own names, off a raw profile.

The serving engine jits every paged executable under its dispatch shape's
name (``pt_paged_b16_t1``: serving/engine.py), writes that name on the
dispatch's ``pt.serve.feed | launch | wait`` spans as ``exe=`` beside
``kind=``, and traces every stage of a step under a ``jax.named_scope``
named ``pt_*``. ``device_time(path)`` turns a ``jax.profiler`` session
that covered a serving run into seconds: an executable by its name, its
runs, its device seconds, its seconds by innermost ``pt_*`` scope, and the
device's idle by what the host loop was doing under it.
``device_time_summary`` prints that as a table. Nothing here exports
anything: request spans on the host's clock are
``observability/tracing.py``'s.

What a TPU profile holds (v5e, jax 0.9.0, looked at by hand): a plane a
device, ``/device:TPU:<n>``; its line ``XLA Modules`` one event an
executable run, named ``jit_<name>(<program id>)``; its line ``XLA Ops``
one event an HLO operation, its text as name, nested (a ``%while`` spans
its body's operations). An operation's ``op_name`` (the JAX name stack it
was traced under, scopes and all) is the stat ``tf_op`` of the event's
METADATA (``XEventMetadata.stats``, beside ``program_id``, ``hlo_category``,
``flops``, ``bytes_accessed``, ``source``), which
``jax.profiler.ProfileData`` does not show: its ``event.stats`` are the
event's own three (``device_offset_ps``, ``device_duration_ps``, ``Time
Scale Multiplier``). ``op_names`` reads that one map off the file's wire
format; events, lines and planes come through ``ProfileData``. Host planes
hold the ``TraceAnnotation`` spans with their keywords as stats.

The two clocks: the device plane lies EARLY against the host planes, by
1.6-1.9 ms in the profiles looked at (a run is shown starting before the
runtime's ``DoEnqueueProgram`` of the same ``run_id``). Durations are not
touched by that; what lies under which host span is. The runtime's own host
event ``CompleteCallbacks`` (stat ``run_id``, as the ``XLA Modules`` event's)
fires when a run has ended, so ``read_profile`` shifts the device plane by
the median of ``CompleteCallbacks``' start less the run's shown end
(``clock_shift_s`` in the result; 0 where the profile holds no such event).
"""
from __future__ import annotations

import bisect
import glob
import os
import re

UNSCOPED = "(unscoped)"
SEAM_NS = 2000                  # a shorter gap lies between two operations
_MODULE = re.compile(r"^(?:jit_)?(.+?)(?:\((\d+)\))?$")
_SPANS = ("pt.serve.feed", "pt.serve.wait")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


# -- the one map ProfileData does not show ---------------------------------
# xplane.proto: XSpace{planes=1}; XPlane{name=2, event_metadata=4,
# stat_metadata=5}, both maps (entry: key=1, value=2); XEventMetadata{name=2,
# stats=5}; XStatMetadata{id=1, name=2}; XStat{metadata_id=1, uint64_value=3,
# int64_value=4, str_value=5, ref_value=7: a stat_metadata id whose NAME is
# the string}.

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"wire type {kind} in an xplane file")
            value, i = buf[i:i + size], i + size
        yield tag >> 3, value


def _map_values(plane, field):
    for f, entry in _fields(plane):
        if f == field:
            yield next(v for k, v in _fields(entry) if k == 2)


def op_names(xspace_bytes):
    """``{device: {(program id, operation's text): op_name}}`` of an
    ``.xplane.pb``'s TPU planes, off their event metadata's ``tf_op`` and
    ``program_id`` stats (a fusion carries its root's ``op_name``)."""
    out = {}
    for f, plane in _fields(memoryview(xspace_bytes)):
        if f != 1:
            continue
        name = next((bytes(v).decode() for k, v in _fields(plane) if k == 2),
                    "")
        m = _DEVICE.match(name)
        if not m:
            continue
        stat_names = {}
        for meta in _map_values(plane, 5):
            got = dict(_fields(meta))
            stat_names[got.get(1, 0)] = bytes(got.get(2, b"")).decode()
        names = out.setdefault(int(m.group(1)), {})
        for meta in _map_values(plane, 4):
            text, program, op_name = "", None, None
            for k, v in _fields(meta):
                if k == 2:
                    text = bytes(v).decode()
                elif k == 5:
                    stat = dict(_fields(v))
                    which = stat_names.get(stat.get(1))
                    if which == "program_id":
                        program = stat.get(3, stat.get(4))
                    elif which == "tf_op":
                        op_name = bytes(stat[5]).decode() if 5 in stat \
                            else stat_names.get(stat.get(7), "")
            if op_name:
                names[program, text] = op_name
    return out


def scope_of(op_name):
    """The innermost ``pt_*`` scope of an operation's ``op_name``
    (``jit(pt_paged_b16_t1)/while/body/pt_ffn/dot_general`` -> ``pt_ffn``),
    or ``(unscoped)``."""
    for part in reversed((op_name or "").split("/")):
        if part.startswith("pt_"):
            return part.rstrip(":")
    return UNSCOPED


def _self_ns(ops):
    """An operation's own nanoseconds: its duration less its direct
    children's, so that a ``%while`` and the body it spans count once."""
    own = [op[2] for op in ops]
    open_ops = []
    for i in sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2])):
        start, dur = ops[i][1], ops[i][2]
        while open_ops and ops[open_ops[-1]][1] + ops[open_ops[-1]][2] <= start:
            open_ops.pop()
        if open_ops:
            own[open_ops[-1]] -= dur
        open_ops.append(i)
    return [max(0, ns) for ns in own]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return out


def _idle_split(busy, window, spans):
    """Nanoseconds of the window's idle gaps (``SEAM_NS`` and longer) under
    a feed span or a wait span that began inside the gap (launch), under a
    wait span that began before the gap (fetch), and the rest (turn)."""
    spans = sorted((s, s + d, name == "pt.serve.wait")
                   for name, s, d, _ in spans)
    ends = [e for _, e, _ in spans]
    launch = fetch = total = 0
    cur = window[0]
    for a, b in busy + [[window[1], window[1]]]:
        if a - cur >= SEAM_NS:
            total += a - cur
            i = bisect.bisect_right(ends, cur)
            while i < len(spans) and spans[i][0] < a:
                s, e, is_wait = spans[i]
                under = max(0, min(a, e) - max(cur, s))
                if is_wait and s < cur:
                    fetch += under
                else:
                    launch += under
                i += 1
        cur = max(cur, b)
    return launch, fetch, total - launch - fetch


def reduce_events(modules, ops, spans, clock_shift_ns=0):
    """The reduction, on plain lists off one device plane and the host's:
    ``modules`` ``[(name, start_ns, dur_ns)]`` (``XLA Modules``), ``ops``
    ``[(text, start_ns, dur_ns, op_name)]`` (``XLA Ops``; ``op_name`` may
    be empty), ``spans`` ``[(name, start_ns, dur_ns, {key: value})]`` (the
    ``pt.serve.feed`` and ``pt.serve.wait`` annotations). Returns::

        {"window_s", "busy_s", "idle_s", "clock_shift_s",
         "executables": {name: {"kind", "runs", "device_s",
                                "scopes": {scope: seconds}}},
         "idle": {"launch_s", "fetch_s", "turn_s", "paged_runs"}}

    ``clock_shift_ns`` is added to every device time before it is laid
    against the spans. The window runs from the first operation's start to
    the last one's end. An operation belongs to the run whose interval
    holds its start (``(no executable)`` where none does); ``kind`` is the
    ``kind=`` of the
    feed spans that name the executable, None for one that no span names
    (a page copy, the host's small jits); ``paged_runs`` counts the runs of
    the executables that spans do name."""
    kinds = {t["exe"]: t.get("kind") for n, _, _, t in spans
             if n == "pt.serve.feed" and "exe" in t}
    runs = sorted((s, s + d, _MODULE.match(name).group(1))
                  for name, s, d in modules)
    starts = [r[0] for r in runs]
    exes = {}

    def exe(name):
        return exes.setdefault(name, {"kind": kinds.get(name), "runs": 0,
                                      "device_s": 0.0, "scopes": {}})

    for start, end, name in runs:
        e = exe(name)
        e["runs"] += 1
        e["device_s"] += (end - start) / 1e9
    for (_, start, _, op_name), own in zip(ops, _self_ns(ops)):
        i = bisect.bisect_right(starts, start) - 1
        e = exe(runs[i][2] if i >= 0 and start < runs[i][1]
                else "(no executable)")
        scope = scope_of(op_name)
        e["scopes"][scope] = e["scopes"].get(scope, 0.0) + own / 1e9
    busy = _union([s + clock_shift_ns, s + d + clock_shift_ns]
                  for _, s, d, _ in ops)
    window = [busy[0][0], busy[-1][1]] if busy else [0, 0]
    launch, fetch, turn = _idle_split(
        busy, window, [sp for sp in spans if sp[0] in _SPANS])
    busy_s = sum(b - a for a, b in busy) / 1e9
    window_s = (window[1] - window[0]) / 1e9
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_s": window_s - busy_s,
            "clock_shift_s": clock_shift_ns / 1e9, "executables": exes,
            "idle": {"launch_s": launch / 1e9, "fetch_s": fetch / 1e9,
                     "turn_s": turn / 1e9,
                     "paged_runs": sum(e["runs"] for n, e in exes.items()
                                       if n in kinds)}}


def _xplane(path):
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        return found[-1]
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no profile at {path}")
    return path


def read_profile(path):
    """``(modules, ops, spans, clock_shift_ns)`` as ``reduce_events`` takes
    them, off the newest ``.xplane.pb`` under ``path`` (or that file): the
    lowest-numbered TPU plane that ran an operation, the ``pt.serve.*``
    spans of every host plane, and the shift that lays the device plane on
    the host's clock (the module's docstring)."""
    import jax
    path = _xplane(path)
    pd = jax.profiler.ProfileData.from_file(path)
    devices, spans, ended, completed = {}, [], {}, {}
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), ([], []))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev[1].extend((ev.name, ev.start_ns, ev.duration_ns)
                                  for ev in line.events)
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        dev[0].append((ev.name, ev.start_ns, ev.duration_ns))
                        run = dict(ev.stats).get("run_id")
                        ended[int(m.group(1)), run] = ev.end_ns
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("pt.serve."):
                        spans.append((ev.name, ev.start_ns, ev.duration_ns,
                                      {k: str(v) for k, v in ev.stats}))
                    elif ev.name == "CompleteCallbacks":
                        completed[dict(ev.stats).get("run_id")] = ev.start_ns
    used = sorted(d for d, (_, ops) in devices.items() if ops)
    if not used:
        raise ValueError(f"{path}: no TPU plane ran an operation (a "
                         f"profile of a CPU run holds no device time)")
    modules, ops = devices[used[0]]
    with open(path, "rb") as f:
        names = op_names(f.read()).get(used[0], {})
    # an operation's program is that of the run it starts in
    runs = sorted((s, s + d, int(_MODULE.match(n).group(2) or 0))
                  for n, s, d in modules)
    starts = [r[0] for r in runs]

    def op_name(text, start):
        i = bisect.bisect_right(starts, start) - 1
        program = runs[i][2] if i >= 0 and start < runs[i][1] else None
        return names.get((program, text), "")

    late = sorted(completed[run] - end for (d, run), end in ended.items()
                  if d == used[0] and run in completed)
    shift = int(late[len(late) // 2]) if late else 0
    return modules, [(t, s, d, op_name(t, s)) for t, s, d in ops], spans, \
        shift


def device_time(path):
    """``reduce_events`` of the profile at ``path``: a directory given to
    ``jax.profiler.start_trace`` (its newest session) or an
    ``.xplane.pb``."""
    return reduce_events(*read_profile(path))


def format_summary(times):
    """``device_time``'s dict as the table ``device_time_summary`` prints."""
    lines = [f"window {times['window_s']:.3f} s   busy {times['busy_s']:.3f}"
             f" s   idle {times['idle_s']:.3f} s "
             f"({100 * times['idle_s'] / max(times['window_s'], 1e-12):.1f}%)"
             f"   device clock shifted {1e3 * times['clock_shift_s']:+.3f} ms"]
    exes = sorted(times["executables"].items(),
                  key=lambda kv: -kv[1]["device_s"])
    for name, e in exes:
        per_run = 1e3 * e["device_s"] / e["runs"] if e["runs"] else 0.0
        lines.append(f"{name}  kind={e['kind'] or '-'}  runs {e['runs']}  "
                     f"device {e['device_s']:.4f} s  {per_run:.3f} ms/run")
        in_ops = sum(e["scopes"].values())
        for scope, s in sorted(e["scopes"].items(), key=lambda kv: -kv[1]):
            lines.append(f"    {scope:<18} {s:9.4f} s  "
                         f"{100 * s / max(in_ops, 1e-12):5.1f}%")
    idle = times["idle"]
    n = idle["paged_runs"]
    lines.append(f"idle by the host loop, over {n} paged runs:")
    for part in ("launch", "fetch", "turn"):
        s = idle[part + "_s"]
        lines.append(f"    {part:<18} {s:9.4f} s  "
                     f"{1e3 * s / n if n else 0.0:7.3f} ms/run")
    return "\n".join(lines)


def device_time_summary(path):
    """Print ``device_time(path)`` as a table (an executable a block,
    longest first, its scopes longest first; then the idle split) and
    return the dict."""
    times = device_time(path)
    print(format_summary(times))
    return times
