"""What every run shares: the traced part of the window, the device's
description, the per-layer readers' context and the result line."""
import json
import shutil
import sys
import tempfile
import time

import jax

from . import loader, peaks, trace as trace_mod, work

WINDOW_SPAN = "bench.traced_window"


class Tracer:
    """Takes the profiler's trace over the first ``seconds`` of the measured
    window of a ``--trace 1`` run (the Python tracer off: only the device,
    the runtime's host events and the benchmark's own spans)."""

    def __init__(self, spans, enabled, seconds):
        self.spans, self.enabled, self.seconds = spans, enabled, seconds
        self.active = False
        self.t0 = self.t1 = None
        self.stop_cost_s = 0.0
        self.dir = None

    def start(self):
        if not self.enabled:
            return
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.spans.annotate = True
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()
        self.active = True
        self.t0 = time.perf_counter()

    def maybe_stop(self, now=None):
        now = time.perf_counter() if now is None else now
        if self.active and now - self.t0 >= self.seconds:
            self.stop()

    def stop(self):
        if not self.active:
            return
        self.t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self.spans.annotate = False
        jax.profiler.stop_trace()
        self.active = False
        self.stop_cost_s = time.perf_counter() - self.t1

    def load(self):
        if not self.enabled:
            return None
        self.stop()
        try:
            return trace_mod.Trace.from_xplane(
                trace_mod.newest_xplane(self.dir), self.spans.names(),
                WINDOW_SPAN)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def require_chips(chips):
    """Exit with another code than 0, and no result, unless JAX finds an
    accelerator with as many chips as the cell asks for."""
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"benchmark: no accelerator: jax.default_backend() is "
                 f"{backend!r}; nothing was run")
    if jax.device_count() < chips:
        sys.exit(f"benchmark: the cell asks for {chips} chips, JAX finds "
                 f"{jax.device_count()}; nothing was run")


def memory_peak_bytes(devices):
    """Peak of the arrays in use on the fullest chip, as ``memory_stats()``
    reports it. What the runtime reserves beside them for the compiled
    programs' own temporaries is a second figure (``peak_bytes_reserved``);
    the two peak at different times, so they are not summed."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def device_info(devices, peak_bytes):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count(), "memory_peak_bytes": int(peak_bytes)}


class Work:
    """The required-work functions a reader sees as ``ctx.work``: the cell's
    family's counts (``families/<family>/work.py``) and, under the same name
    as ever, the harness's ``roofline_seconds``."""
    roofline_seconds = staticmethod(work.roofline_seconds)

    def __init__(self, family_work):
        self._family_work = family_work

    def __getattr__(self, name):
        return getattr(self._family_work, name)


class Context:
    """What a per-layer metric's reader gets: the cell's files, the peaks of
    this device, the required-work functions of the cell's model family, the
    reduced trace (or None), the benchmark's host spans, the program's
    counters over the window and the run's own facts."""

    def __init__(self, cell, devices, window_s, spans, counters, facts,
                 trace=None, traced=None, stop_cost_s=0.0):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.devices = devices
        self.chips = cell.chips
        self.on_chip = devices[0].platform == "tpu"
        self.peaks = peaks.peaks_for(devices[0].device_kind) \
            if self.on_chip else None
        self.work = Work(cell.family.work)
        self.window_s = window_s
        # the window less what writing the trace out took inside it: what a
        # rate read in the traced run is taken over
        self.work_window_s = window_s - stop_cost_s
        self.spans = spans
        self.counters = counters
        self.facts = facts
        self.trace = trace
        self.traced = traced          # (t0, t1) of the traced part, host clock


def per_layer_metrics(cell, ctx):
    """{name: {"value", "unit"}} for the cell's per-layer metrics. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer():
        value = loader.load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end_metrics(cell, values):
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end()}


def off_chip_names(metrics):
    """A run that is not on a TPU (the rehearsal) writes nothing under a
    device metric's name."""
    return {"rehearsal_cpu." + k: v for k, v in metrics.items()}


def breakdown(trace):
    if trace is None:
        return None
    devs = trace.used_devices()
    idlest = max(devs, key=trace.idle_share)
    return {"device_ops": trace.op_seconds(idlest),
            "idle_gaps": trace.idle_gaps(idlest)}


def emit(checks, attempted, failed, metrics, device, brk=None, extra=None):
    """The numbers compared on standard error, then the one result line as
    the last line of standard output, ``compared`` last in it."""
    for line in checks.lines():
        print(line, file=sys.stderr)
    sys.stderr.flush()
    out = {"correct": checks.correct, "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if brk:
        out["breakdown"] = brk
    if extra:
        out.update(extra)
    out["compared"] = checks.as_dict()
    print(json.dumps(out), flush=True)
    return out
