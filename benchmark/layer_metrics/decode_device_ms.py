"""Mean device time of one decode dispatch: the mean duration of the ``XLA
Modules`` events (one an executable run), whole inside the traced window, of
the executables that the program's own ``pt.serve.feed`` spans name with
``kind=decode``. The program jits each paged executable under its dispatch
shape's name and writes that name on the dispatch's spans as ``exe=``; a run
of it reads ``jit_<exe>(<fingerprint>)`` on the device's line. So a dispatch
is found by its executable's own name, whatever the host overlaps with it,
and what is read is the device's time alone: the host's feed, launch and
fetch are not in it (``decode_step_ms`` is the host's time of the same
dispatch).

Nothing to read off the chip, without a trace, or from a program whose spans
carry no ``exe=`` (the parent of the PR that brought it)."""
FEED = "pt.serve.feed#"


def span_tags(name):
    """``{"kind": "decode", "exe": "pt_paged_b16_t1"}`` of the host span
    ``pt.serve.feed#kind=decode,exe=pt_paged_b16_t1#`` (``Trace.host``'s
    form: what the annotation carries follows its name between hashes)."""
    parts = name.split("#")
    if len(parts) < 2:
        return {}
    return dict(kv.split("=", 1) for kv in parts[1].split(",") if "=" in kv)


def executables(trace):
    """``{exe: kind}`` by the program's feed spans: which executables the
    program dispatched, and as what."""
    out = {}
    for name, _, _ in trace.host:
        if name.startswith(FEED):
            tags = span_tags(name)
            if "exe" in tags and "kind" in tags:
                out[tags["exe"]] = tags["kind"]
    return out


def runs(trace, dev, exes):
    """``[[start_ns, dur_ns], ...]`` of the runs, whole inside the traced
    window, of the executables named in ``exes``."""
    heads = tuple(f"jit_{e}(" for e in exes)
    w0, w1 = trace.window
    return [[s, d] for name, s, d in trace.devices[dev]["modules"]
            if name.startswith(heads) and s >= w0 and s + d <= w1]


def device_ms(ctx, kind):
    """Mean milliseconds a run of the executables of ``kind``, or None."""
    tr = ctx.trace
    if tr is None or not ctx.on_chip or not tr.used_devices():
        return None
    exes = [e for e, k in executables(tr).items() if k == kind]
    found = runs(tr, tr.used_devices()[0], exes)
    if not found:
        return None
    return sum(d for _, d in found) / len(found) / 1e6


def read(ctx):
    return device_ms(ctx, "decode")
