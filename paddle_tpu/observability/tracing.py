"""Per-request span tracing for the serving stack.

Every ``Request`` served with ``FLAGS_serving_trace`` on carries a
``RequestTrace``: an append-only list of spans recorded host-side at the
points the engine already timestamps anyway — queue wait (submit→admit),
each prefill chunk, each decode step, CoW/prefix-cache events, and the
self-healing hops (requeue / replay / snapshot-restore). Span timestamps
REUSE the exact ``perf_counter`` values the SLO ledger records
(``submit_t`` / ``first_token_t`` / ``finish_t``), so an exported trace
reconciles with the request's recorded TTFT and latency to the float —
"why was THIS request's TTFT 900ms" is answered by reading its spans.

Traces survive engine snapshots: ``RequestTrace.to_state()`` rides in
``Request.to_state()``, and ``Engine.load_state_dict`` shifts the spans
with the same clock re-anchoring it applies to the request timestamps —
a kill-and-resume request's trace shows the pre-kill spans, the restore
hop, and the post-restore spans on one consistent timeline.

Finished traces land in a bounded module ring (``collect``) and export as
Perfetto-loadable Chrome-trace JSON (``export_perfetto``) or stream to a
structured JSONL sink (``add_sink`` / ``JsonlTraceSink``). Everything is
host-side: tracing on/off never changes a compiled executable, a traced
operand, or a trace counter.

A tracing engine also hands in every boundary's phase spans
(``collect_boundary``: ``pt.serve.step`` around ``pt.serve.admit | feed |
wait | emit``, the floats of the engine's phase clock). They export onto one
engine track (pid = engine tag, thread ``boundaries``), so the trace shows
what the host did between two ``decode_step`` spans of a request.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque


class RequestTrace:
    """Append-only span list for one request. A span is a dict
    ``{"name", "t0", "t1", ...meta}`` with perf_counter-domain seconds;
    ``t1 == t0`` marks an instant event."""

    __slots__ = ("request_id", "spans")

    def __init__(self, request_id, spans=None):
        self.request_id = int(request_id)
        self.spans = list(spans or ())

    def span(self, name, t0, t1, **meta):
        ev = {"name": name, "t0": float(t0), "t1": float(t1)}
        if meta:
            ev.update(meta)
        self.spans.append(ev)
        return ev

    def instant(self, name, t=None, **meta):
        t = time.perf_counter() if t is None else t
        return self.span(name, t, t, **meta)

    def tail(self):
        """Latest span end, or None — where a post-requeue queue span
        starts so hops never overlap the pre-drain timeline."""
        return max((ev["t1"] for ev in self.spans), default=None)

    def shift(self, dt):
        """Re-anchor every span onto a new clock origin (the engine-restore
        companion of the request-timestamp shift)."""
        for ev in self.spans:
            ev["t0"] += dt
            ev["t1"] += dt

    def duration_sum(self, names=None):
        return sum(ev["t1"] - ev["t0"] for ev in self.spans
                   if names is None or ev["name"] in names)

    # -- snapshot ------------------------------------------------------------
    def to_state(self):
        return [dict(ev) for ev in self.spans]

    @classmethod
    def from_state(cls, request_id, spans):
        return cls(request_id, [dict(ev) for ev in spans or ()])

    def copy(self):
        return RequestTrace.from_state(self.request_id, self.spans)


# -- finished-trace collection ------------------------------------------------

_lock = threading.Lock()
_done = deque(maxlen=4096)
_seen = set()        # request_ids currently in the ring: first-wins dedup
_steps = deque(maxlen=4096)   # engine boundaries: {"engine", "spans"}
_sinks = []
# the engine track's thread id: beside the request ids, which count up from 0
BOUNDARY_TID = 2 ** 31 - 1


def _maxlen():
    from ..flags import _FLAGS
    return int(_FLAGS.get("FLAGS_trace_buffer", 4096) or 4096)


def collect(req, engine_tag="engine"):
    """Archive a resolved request's trace (called by ``Engine._resolve``;
    no-op when the request is untraced). The record is self-contained —
    the SLO numbers ride along so sinks and exports never need the
    Request back.

    First result wins per request_id (mirroring the supervisor's delivery
    dedup): a snapshot-respawned replica recomputing already-archived
    work, or a hygiene-cancel of a stale snapshot copy, does not mint a
    duplicate timeline. The dedup window is the RETAINED ring
    (FLAGS_trace_buffer): once a record is evicted its id is forgotten —
    a bounded set, not a forever-growing one — so a recompute arriving
    thousands of requests later can re-archive; downstream consumers that
    join on request_id should keep the first record they saw."""
    trace = getattr(req, "trace", None)
    if trace is None:
        return None
    rec = {
        "request_id": int(req.request_id),
        "engine": str(engine_tag),
        "finish_reason": req.finish_reason,
        "requeue_count": int(getattr(req, "requeue_count", 0)),
        "ttft": (None if req.first_token_t is None or req.submit_t is None
                 else req.first_token_t - req.submit_t),
        "latency": (None if req.finish_t is None or req.submit_t is None
                    else req.finish_t - req.submit_t),
        "tokens": len(req.tokens),
        "spans": trace.to_state(),
    }
    with _lock:
        global _done
        if rec["request_id"] in _seen:
            return None
        ml = _maxlen()
        if _done.maxlen != ml:                    # FLAGS_trace_buffer moved
            kept = list(_done)[max(0, len(_done) - ml):]
            _done = deque(kept, maxlen=ml)
            _seen.intersection_update(r["request_id"] for r in kept)
        if len(_done) == _done.maxlen:
            # evict explicitly so the dedup set tracks the ring (deque
            # maxlen would evict silently); O(1) at steady state
            _seen.discard(_done.popleft()["request_id"])
        _done.append(rec)
        _seen.add(rec["request_id"])
        sinks = list(_sinks)
    for sink in sinks:
        try:
            sink(rec)
        except Exception:  # noqa: BLE001 — a broken sink must not
            pass           # unwind the serving step
    from .registry import REGISTRY
    REGISTRY.counter("serving.trace.requests").inc()
    REGISTRY.counter("serving.trace.spans").inc(len(rec["spans"]))
    return rec


def collect_boundary(engine_tag, spans):
    """Archive one engine boundary's phase spans (called by ``Engine.step``
    of a tracing engine). The ring is bounded by ``FLAGS_trace_buffer``
    like the request ring."""
    with _lock:
        global _steps
        ml = _maxlen()
        if _steps.maxlen != ml:
            _steps = deque(_steps, maxlen=ml)
        _steps.append({"engine": str(engine_tag), "spans": spans})


def traces():
    """Snapshot of the collected finished-request traces (newest last)."""
    with _lock:
        return [dict(r, spans=[dict(s) for s in r["spans"]]) for r in _done]


def boundaries():
    """Snapshot of the collected engine boundaries (newest last)."""
    with _lock:
        return [dict(r, spans=[dict(s) for s in r["spans"]]) for r in _steps]


def clear():
    with _lock:
        _done.clear()
        _seen.clear()
        _steps.clear()


def add_sink(fn):
    """Register a callable invoked with each finished trace record."""
    with _lock:
        _sinks.append(fn)
    return fn


def remove_sink(fn):
    with _lock:
        try:
            _sinks.remove(fn)
        except ValueError:
            pass


class JsonlTraceSink:
    """Structured JSONL sink: one line per finished request. Register with
    ``add_sink(JsonlTraceSink(path))``; ``close()`` removes + flushes."""

    def __init__(self, path):
        self.path = path
        self._f = open(path, "a")
        self._lock = threading.Lock()
        add_sink(self)

    def __call__(self, rec):
        line = json.dumps(rec)
        with self._lock:
            self._f.write(line + "\n")
            self._f.flush()

    def close(self):
        remove_sink(self)
        with self._lock:
            self._f.close()


# -- Perfetto / Chrome-trace export -------------------------------------------

def chrome_events(records=None):
    """Chrome-trace event list from finished-trace records (default: the
    collected ring, and with it the collected engine boundaries). pid =
    engine tag, tid = request id or the engine's ``boundaries`` thread,
    ts/dur in µs on the perf_counter timeline; instants export as ph='i'."""
    events = []
    seen_pids = {}
    seen_tids = set()
    for rec in (traces() + boundaries() if records is None else records):
        new_pid = rec["engine"] not in seen_pids
        pid = seen_pids.setdefault(rec["engine"], len(seen_pids) + 1)
        tid = rec.get("request_id", BOUNDARY_TID)
        for ev in rec["spans"]:
            ts = ev["t0"] * 1e6
            dur = (ev["t1"] - ev["t0"]) * 1e6
            args = {k: v for k, v in ev.items()
                    if k not in ("name", "t0", "t1")}
            if dur <= 0:
                events.append({"name": ev["name"], "ph": "i", "s": "t",
                               "pid": pid, "tid": tid, "ts": ts,
                               "args": args})
            else:
                events.append({"name": ev["name"], "ph": "X", "pid": pid,
                               "tid": tid, "ts": ts, "dur": dur,
                               "args": args})
        if new_pid:
            events.append({"name": "process_name", "ph": "M", "pid": pid,
                           "args": {"name": f"serving:{rec['engine']}"}})
        if (pid, tid) not in seen_tids:
            seen_tids.add((pid, tid))
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid,
                           "args": {"name": "boundaries"
                                    if tid == BOUNDARY_TID
                                    else f"request {tid}"}})
    return events


def export_perfetto(path, records=None):
    """Write the collected request traces and engine boundaries as
    Chrome-trace JSON (loads in Perfetto / chrome://tracing / TensorBoard).
    Returns the path."""
    payload = {"traceEvents": chrome_events(records),
               "displayTimeUnit": "ms"}
    with open(path, "w") as f:
        json.dump(payload, f)
    return path
