"""The reduction from a profiler trace to numbers: device busy time, kernel
time, self time of device operations, and the idle gaps named by the host
span that covered them.

A trace is first brought into a small plain form (``Trace``), which a test
checks on a recorded trace kept under benchmark/tests/; every number comes
from that form, so every PR computes it in the same way.

What a v5e trace looks like (looked at by hand, PR 24): device planes are
named ``/device:TPU:<n>``; their line ``XLA Ops`` holds one event per HLO
operation with its text as name, nested (a ``%while`` spans its body's
operations); ``XLA Modules`` holds one event per executable run. Host
planes hold ``TraceAnnotation`` spans under their given names, on the same
clock. A Pallas kernel compiled by Mosaic is a custom call whose text holds
``custom_call_target="tpu_custom_call"``; its name is the JAX scope it was
traced under."""
import bisect
import glob
import os
import re

MOSAIC = 'custom_call_target="tpu_custom_call"'
PROGRAM_SPANS = "pt."    # the prefix of the program's own host spans
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WRAPPER = re.compile(r"^%(while|conditional|call)[.\s=]")
NAME_KEEP = 400          # characters of an operation's text that are kept


def _keep(text):
    """The head of an operation's text, with the Mosaic mark kept if the cut
    would lose it."""
    head = text[:NAME_KEEP]
    return head + " " + MOSAIC if MOSAIC in text and MOSAIC not in head \
        else head


def op_name(text):
    """``%fusion.12`` of ``%fusion.12 = bf16[...] fusion(...)``."""
    return text.split(" ", 1)[0]


class Trace:
    """devices: {device index: {"ops": [[text, start_ns, dur_ns], ...],
    "modules": [[name, start_ns, dur_ns], ...]}}; host: [[span name,
    start_ns, dur_ns], ...]; window: [start_ns, end_ns] of the traced part
    of the measured window, on the same clock."""

    def __init__(self, devices, host, window):
        self.devices = {int(k): v for k, v in devices.items()}
        self.host = host
        self.window = window

    # -- plain form in and out ----------------------------------------------
    def to_json(self):
        return {"devices": self.devices, "host": self.host,
                "window": self.window}

    @classmethod
    def from_json(cls, d):
        return cls(d["devices"], d["host"], d["window"])

    @classmethod
    def from_xplane(cls, path, span_names, window_span):
        """Of the host planes it keeps the benchmark's own spans
        (``span_names``) and the program's (names that start with ``pt.``;
        what the annotation carries beside its name follows it as
        ``#key=value,...#``)."""
        import jax
        pd = jax.profiler.ProfileData.from_file(path)
        devices, host = {}, []
        names = set(span_names) | {window_span}
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                dev = devices.setdefault(int(m.group(1)),
                                         {"ops": [], "modules": []})
                for line in plane.lines:
                    key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                        line.name)
                    if key:
                        dev[key] = [[_keep(ev.name), ev.start_ns,
                                     ev.duration_ns] for ev in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in names:
                            host.append([ev.name, ev.start_ns, ev.duration_ns])
                        elif ev.name.startswith(PROGRAM_SPANS):
                            args = ",".join(f"{k}={v}" for k, v in ev.stats)
                            name = ev.name + (f"#{args}#" if args else "")
                            host.append([name, ev.start_ns, ev.duration_ns])
        win = [h for h in host if h[0] == window_span]
        if not win:
            raise RuntimeError("the trace holds no window span")
        window = [win[0][1], win[0][1] + win[0][2]]
        host = [h for h in host if h[0] != window_span]
        return cls(devices, host, window)

    # -- intervals ------------------------------------------------------------
    def _clip(self, start, dur):
        a, b = max(start, self.window[0]), min(start + dur, self.window[1])
        return (a, b) if b > a else None

    def _union(self, events):
        """Merged [start, end] intervals of events, clipped to the window."""
        iv = sorted(c for c in (self._clip(s, d) for _, s, d in events) if c)
        out = []
        for a, b in iv:
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return out

    @staticmethod
    def _measure(iv):
        return sum(b - a for a, b in iv) / 1e9

    @staticmethod
    def _minus(a_iv, b_iv):
        """Parts of the intervals a_iv that no interval of b_iv covers."""
        out, j = [], 0
        for a, b in a_iv:
            cur = a
            while j < len(b_iv) and b_iv[j][1] <= cur:
                j += 1
            k = j
            while k < len(b_iv) and b_iv[k][0] < b:
                if b_iv[k][0] > cur:
                    out.append([cur, b_iv[k][0]])
                cur = max(cur, b_iv[k][1])
                k += 1
            if cur < b:
                out.append([cur, b])
        return out

    # -- numbers ----------------------------------------------------------------
    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self, dev):
        """Seconds of the window in which an operation ran on the device."""
        return self._measure(self._union(self.devices[dev]["ops"]))

    def idle_share(self, dev):
        return 1.0 - self.busy_s(dev) / self.window_s

    def leaf_ops(self, dev):
        """Operations that span no other (a ``%while`` spans its body)."""
        return [e for e in self.devices[dev]["ops"] if not WRAPPER.match(e[0])]

    def kernel_seconds(self, dev, pred):
        """Summed duration inside the window of the operations whose text
        ``pred`` accepts, and how many there were."""
        tot, n = 0.0, 0
        for text, s, d in self.devices[dev]["ops"]:
            if pred(text):
                c = self._clip(s, d)
                if c:
                    tot += (c[1] - c[0]) / 1e9
                    n += 1
        return tot, n

    def op_seconds(self, dev, top=10):
        """[[operation, seconds], ...]: leaf operations by summed time."""
        tot = {}
        for text, s, d in self.leaf_ops(dev):
            c = self._clip(s, d)
            if c:
                key = op_name(text) + (" (mosaic)" if MOSAIC in text else "")
                tot[key] = tot.get(key, 0.0) + (c[1] - c[0]) / 1e9
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:top]

    def idle_gaps(self, dev, top=10):
        """[[host span, seconds], ...]: the device's idle time in the window
        by the benchmark's host span that covered the middle of each gap,
        longest first. The benchmark's spans do not nest, so the span is the
        last one that started before the middle (the program's spans nest
        inside them and name no gap yet); gaps under two microseconds are
        the seams between operations and are summed under one name."""
        busy = self._union(self.devices[dev]["ops"])
        gaps = self._minus([list(self.window)], busy)
        spans = sorted((h for h in self.host
                        if not h[0].startswith(PROGRAM_SPANS)),
                       key=lambda h: h[1])
        starts = [h[1] for h in spans]
        tot = {}
        for a, b in gaps:
            if b - a < 2000:
                name = "(seams between operations)"
            else:
                mid = (a + b) / 2
                i = bisect.bisect_right(starts, mid) - 1
                name = spans[i][0] if i >= 0 and \
                    mid <= spans[i][1] + spans[i][2] else "(no span)"
            tot[name] = tot.get(name, 0.0) + (b - a) / 1e9
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:top]

    def used_devices(self):
        return sorted(d for d, v in self.devices.items() if v["ops"])


def newest_xplane(logdir):
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")), key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no trace under {logdir}")
    return paths[-1]
