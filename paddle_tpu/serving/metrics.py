"""Serving observability counters (profiler counter pattern of
dispatch/comm/mp_comm/fault: a module-level ledger, snapshot via
`profiler.serving_counters()`, one-line `profiler.serving_summary()`).

The ``*_traces`` counters are the engine's no-recompile audit trail: each
jitted body bumps its counter only when actually TRACED, so after warmup (one
trace a dispatch shape) the counts must freeze — admission, eviction and
sampling-param changes reuse the cached executables.
"""
from __future__ import annotations

import threading
import time
from collections import deque

import numpy as np
from jax.profiler import TraceAnnotation

_lock = threading.Lock()


def _zero():
    return {
        # request lifecycle
        "submitted": 0, "admitted": 0, "completed": 0, "rejected": 0,
        "expired": 0, "cancelled": 0,
        "finished_stop": 0, "finished_length": 0,
        # executables: fused chunk/decode dispatches. paged_traces freezes
        # after warmup at 1 (the [B,1] decode shape) + one [1,rung] trace
        # per chunk-ladder rung actually used; copy_traces at <= 1.
        "paged_steps": 0, "paged_traces": 0,
        # paged dispatches in which some row both emitted and sampled: the
        # only ones whose sampling tail (cuts, sorts, Gumbel draw over
        # [slots, vocab]) ran; in the others the step took the argmax
        "sampled_steps": 0,
        # host-to-device arrays the engine's dispatches sent and device-to-
        # host arrays they fetched, counted where they are sent and
        # fetched (Engine._upload / _fetch). A decode or chunk dispatch
        # sends its slot operands as one buffer and fetches its small
        # outputs as one (serving/operands.py): one of each a paged step,
        # two more uploads with a quantised pool's scale tables. A
        # speculative engine's draft and verify still send theirs one by one
        "paged_uploads": 0, "paged_fetches": 0,
        # page-table entries of the [B, 1] decode dispatches, and those the
        # read the step was built with visits: the live pages of each slot
        # under the decode kernel, the whole table under the gather read
        "decode_pages_table": 0, "decode_pages_swept": 0,
        "chunk_steps": 0, "prefill_chunks": 0,
        "cow_copies": 0, "copy_traces": 0,
        # prefix cache
        "prefix_lookups": 0, "prefix_hits": 0, "prefix_tokens_reused": 0,
        # page occupancy observed at step boundaries
        "pages_inuse_sum": 0, "pages_inuse_max": 0, "pages_total": 0,
        "page_boundaries": 0,
        # per-prefill padded-token waste: n_chunks*chunk - prefilled_tokens
        # (< page_size per request)
        "prefill_padded_tokens": 0, "prefill_padded_reqs": 0,
        "prefill_padded_max": 0,
        # self-healing: engine snapshots + drain/replay recovery ledger.
        # "dropped" must stay 0 through any preemption/kill/rolling-restart
        # story — every in-flight request either completes or is replayed.
        "snapshots": 0, "snapshot_restores": 0, "preempt_drains": 0,
        "requeued": 0, "replayed": 0, "respawns": 0,
        "stale_failovers": 0, "rolling_restarts": 0, "dropped": 0,
        # serving anomaly guard (FLAGS_serving_anomaly_policy=quarantine):
        # slots whose logits went non-finite, resolved "error" at the
        # boundary with neighbors bitwise-stable
        "anomalies_quarantined": 0,
        # SLO traffic management (serving/slo.py): queued work shed under
        # sustained overload, running slots preempted for an interactive
        # deadline, router-side rate-limit refusals, autoscale actions and
        # hot weight swaps. Queue-wait sums make shed/expired traffic
        # visible: how long refused work sat in queue before the verdict.
        "shed": 0, "preempted": 0, "rate_limited": 0,
        "scale_ups": 0, "scale_downs": 0, "weight_swaps": 0,
        # queue-wait is recorded only for requests refused FROM THE QUEUE
        # (up-front ShedError refusals and mid-flight expiries carry no
        # queue wait), so the means divide by these sample counts, not by
        # the total shed/expired tallies
        "shed_queue_wait_s": 0.0, "shed_queue_waits": 0,
        "expired_queue_wait_s": 0.0, "expired_queue_waits": 0,
        # quantized serving (serving/quant.py): scale-table footprint,
        # per-chip KV bytes one token costs at the engine's dtype config
        # (the capacity-per-chip gauge), and the max logit drift the gate
        # harness measured against the fp engine (0.0 until a harness
        # runs). Dtype LABELS live in _quant_info (counters stay numeric
        # so the Prometheus family export is untouched).
        "quant_scale_bytes": 0, "quant_kv_bytes_per_token": 0,
        "quant_logit_drift_max": 0.0,
        # tensor-parallel serving (serving/mp_forward.py): per-dispatch
        # STATIC collective schedule of the mp rung — wire bytes moved,
        # collectives issued, Pallas fused-kernel dispatches (fused rung
        # only). The same records also feed the training-shared
        # profiler.mp_comm_counters() ledger.
        "mp_steps": 0, "mp_collectives": 0, "mp_wire_bytes": 0,
        "mp_fused_dispatches": 0,
        # disaggregated serving (serving/kv_transfer.py): prefill-worker
        # handoffs, decode-worker transfer installs/seats, wire bytes at
        # the pool's storage dtype, and the router's prefix-affinity
        # decisions. A routed affinity hit means the transfer was SKIPPED
        # — the decode replica's cache already held the pages.
        "prefill_handoffs": 0, "transfers": 0, "transfer_pages": 0,
        "transfer_bytes": 0, "transfer_installs": 0,
        "transfer_time_s": 0.0,
        # KV wire integrity (FLAGS_kv_transfer_crc): payloads whose bytes
        # failed the stamped CRC32 at install time — refused, never seated
        "transfer_crc_refusals": 0,
        "affinity_hits": 0, "disagg_fallbacks": 0, "role_rebalances": 0,
        # page read/write executables for the transfer path (memoized like
        # every other builder — frozen after warmup)
        "read_traces": 0, "write_traces": 0,
        # speculative decoding (FLAGS_serving_speculate_k): draft/verify
        # dispatch tallies, proposed vs accepted draft tokens, and the
        # tokens every speculative boundary actually emitted. The two
        # trace counters are the spec engine's no-recompile audit trail
        # (one draft + one verify executable, memoized per config); on a
        # plain engine the whole family stays 0 — the flags-off gate.
        "draft_dispatches": 0, "verify_dispatches": 0,
        "spec_proposed": 0, "spec_accepted": 0, "spec_tokens_out": 0,
        "spec_draft_traces": 0, "spec_verify_traces": 0,
        # many-model serving (serving/adapters.py): adapter residency ops
        # (hot load / evict / in-place swap — all zero-retrace), admission
        # boundaries a request spent blocked on a non-resident adapter,
        # and the residency gauges (resident count, HBM bytes their
        # rank-padded delta rows occupy). Capacity labels (slots/rank/
        # per-adapter row bytes) live in _adapter_info.
        "adapter_loads": 0, "adapter_evicts": 0, "adapter_swaps": 0,
        "adapter_admit_blocked": 0,
        "adapters_resident": 0, "adapter_delta_bytes": 0,
        # tokens / time. decode_time_s / prefill_time_s are feed + wait of
        # the decode-side (decode, draft, verify) and of the prefill-side
        # (chunk) dispatches: each ends when the dispatch's outputs are on
        # the host
        "tokens_out": 0,
        "decode_time_s": 0.0, "prefill_time_s": 0.0,
        # phase clock of Engine.step (PhaseClock below): seconds of every
        # boundary (count: "boundaries") and of its disjoint phases, which
        # with a remainder (ledger bumps, snapshots) sum to step_s;
        # launch_s is the part of feed_s inside the jitted calls themselves
        "step_s": 0.0, "admit_s": 0.0, "feed_s": 0.0, "wait_s": 0.0,
        "emit_s": 0.0, "launch_s": 0.0,
        # request-level: submit -> admission of every admitted request, and
        # admission -> first token of every fresh first token (a requeued
        # or replayed request's first token counts once, as in observe_ttft)
        "admit_queue_wait_s": 0.0, "admit_queue_waits": 0,
        "prefill_span_s": 0.0, "first_tokens": 0,
        # expert layers (a model whose paged step returns routing
        # statistics, models/xing4.py), by dispatch kind: expert layers
        # dispatched, (token, expert) assignments of real tokens to held
        # experts, and held experts that got at least one token, each
        # summed over expert layers and dispatches; moe_load_max is the
        # most tokens one expert of one layer got in one dispatch
        "moe_layer_dispatches_decode": 0, "moe_layer_dispatches_chunk": 0,
        "moe_assignments_decode": 0, "moe_assignments_chunk": 0,
        "moe_touched_decode": 0, "moe_touched_chunk": 0,
        "moe_load_max": 0,
        # a cache of several groups of layers, one of them a window group
        # (models/afmoe.py), at each admission: pages mapped for the
        # request in the groups with no window and in the window groups
        # (a ring a slot), and what the latter would have mapped with no
        # cap, each times its group's layers
        "kv_pages_mapped_full": 0, "kv_pages_mapped_window": 0,
        "kv_pages_unwindowed": 0,
        # admission ledger of a model with a state group: admissions (each
        # binds one slot's state), the bytes they bound (pages mapped times
        # their group's layers times a page's bytes, plus the state rows of
        # the slot) and what the same lifetimes would have mapped had every
        # state layer kept rows a token as the first paged group's do
        "state_slots_bound": 0, "cache_bytes_bound": 0,
        "cache_bytes_all_paged": 0,
        # a model of selective-scan layers (models/jamba.py), by the host's
        # copy of each dispatch's operands: the real positions of every
        # chunk dispatch, the live slots of every [B, 1] decode dispatch
        # (each once a dispatch, not once a layer)
        "ssm_scan_positions": 0, "ssm_step_slots": 0,
        # occupancy: sum of active slots over decode steps / (steps * slots)
        "active_slot_steps": 0, "slot_steps": 0,
        # queue depth observed at step boundaries
        "queue_depth_sum": 0, "queue_depth_max": 0, "boundaries": 0,
    }


_C = _zero()
# mp rung labels (summary display only — counters stay numeric so the
# Prometheus family export is untouched): set by the last mp engine built
_mp_info = {}
# quant dtype labels (summary display): set by the last quantized engine
_quant_info = {}
# adapter capacity labels (summary display + registry export): slot count,
# padded rank, per-adapter row bytes — engine CONFIGURATION like _mp_info,
# set once at build and surviving reset_serving_counters
_adapter_info = {}
# per-adapter token tally (lazy: an adapter id appears once a request it
# served frees its slot) — feeds the per-adapter token-share gauges that
# make WFQ-across-adapters fairness observable
_adapter_tokens = {}
# ring buffers: percentiles track the LAST window of traffic, not the
# first — a long-running server must surface a late latency regression
_MAX_SAMPLES = 65536
_ttft = deque(maxlen=_MAX_SAMPLES)      # seconds
# per-priority-class TTFT rings (lazy: a class appears once it has a
# sample) — the SLO story is per-class: the chaos gate holds the
# INTERACTIVE p99 while best_effort visibly degrades
_ttft_cls = {}


def bump(name, n=1):
    with _lock:
        _C[name] += n


def set_mp_info(mp, backend):
    """Record the mp rung shape for ``serving_summary()`` display (kept
    out of the counters dict: labels are strings, counters numeric)."""
    with _lock:
        _mp_info["mp"] = int(mp)
        _mp_info["backend"] = str(backend)


def set_quant_info(weight_dtype, kv_dtype, scale_bytes=0,
                   kv_bytes_per_token=0):
    """Record the serving dtype config (labels) plus its numeric gauges
    (scale-table bytes, per-chip KV bytes/token) — set at engine build,
    visible in ``serving_summary()`` and, numerically, through the
    registry/Prometheus export."""
    with _lock:
        _quant_info["weight_dtype"] = str(weight_dtype)
        _quant_info["kv_dtype"] = str(kv_dtype)
        _C["quant_scale_bytes"] = int(scale_bytes)
        _C["quant_kv_bytes_per_token"] = int(kv_bytes_per_token)


def set_adapter_info(slots, rank, row_bytes):
    """Record the adapter-capacity config (serving/adapters.py) — slot
    count, padded rank, per-adapter delta row bytes — set once at engine
    build. Configuration labels like ``_mp_info``: they survive
    ``reset_serving_counters`` so a benchmark resetting counters between
    rungs keeps the summary's capacity context."""
    with _lock:
        _adapter_info["slots"] = int(slots)
        _adapter_info["rank"] = int(rank)
        _adapter_info["row_bytes"] = int(row_bytes)


def set_adapter_residency(resident, delta_bytes):
    """Residency gauges, rewritten after every load/evict/swap: how many
    adapters are resident and how many HBM bytes their (rank-padded)
    delta rows actually occupy."""
    with _lock:
        _C["adapters_resident"] = int(resident)
        _C["adapter_delta_bytes"] = int(delta_bytes)


def observe_adapter_tokens(adapter_id, n):
    """Tally ``n`` emitted tokens against ``adapter_id`` (0 = base model)
    — recorded when a slot frees, so the per-adapter token-share gauges
    reflect work actually delivered per model."""
    with _lock:
        _adapter_tokens[int(adapter_id)] = (
            _adapter_tokens.get(int(adapter_id), 0) + int(n))


def observe_logit_drift(drift):
    """Max-track the logit drift a gate harness measured (fp engine vs
    the quantized engine on the same input) — the ``serving_summary()``
    "quant:" segment surfaces it next to the dtype config."""
    with _lock:
        _C["quant_logit_drift_max"] = max(_C["quant_logit_drift_max"],
                                          float(drift))


def observe_moe(kind, layers, assignments, touched, load_max):
    """One dispatch's routing statistics (``kind`` chunk | decode) over its
    ``layers`` expert layers."""
    with _lock:
        _C[f"moe_layer_dispatches_{kind}"] += int(layers)
        _C[f"moe_assignments_{kind}"] += int(assignments)
        _C[f"moe_touched_{kind}"] += int(touched)
        _C["moe_load_max"] = max(_C["moe_load_max"], int(load_max))


def add_time(name, dt):
    with _lock:
        _C[name] += dt


class _Launch:
    """``PhaseClock.launch()``: one object a clock, entered once a
    dispatch."""
    __slots__ = ("_clock", "_ann", "_t")

    def __init__(self, clock):
        self._clock = clock

    def __enter__(self):
        self._ann = TraceAnnotation("pt.serve.launch", **self._clock._tags)
        self._ann.__enter__()
        self._t = time.perf_counter()

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t
        self._ann.__exit__(*exc)
        if self._clock._name == "feed":     # warm_up dispatches in no step
            self._clock.add("launch_s", dt)


class PhaseClock:
    """The one timing idiom of ``Engine.step``: a mark-based clock that
    takes one ``perf_counter()`` per phase edge, keeps the boundary's sums
    in a local dict and adds them to the ledger with ONE lock acquisition
    in ``finish()``. Every phase is also a ``jax.profiler.TraceAnnotation``
    (``pt.serve.step`` around ``pt.serve.admit|feed|wait|emit``; a
    dispatch's feed and wait carry ``kind=`` and ``exe=``, the name its
    executable runs under on the device's ``XLA Modules`` line less the
    ``jit_``), so inside a profiler session the phases lie on the device
    trace's clock; outside one an annotation is a no-op check. With
    ``keep_spans`` the same instants are kept as spans for the engine track
    of the exported trace.

    The phases of a boundary are disjoint: opening one closes the one
    before it at the same instant. ``feed(kind, time_to, exe)`` opens a
    dispatch; its feed and its ``wait()`` also add to ``time_to``
    (``decode_time_s`` or ``prefill_time_s``). ``launch()`` is no phase: a
    ``pt.serve.launch`` span NESTED in the open feed, around the jitted
    call alone, whose seconds go to ``launch_s`` with the boundary's one
    flush (two ``perf_counter()`` a dispatch, no lock)."""

    def __init__(self, keep_spans=False):
        self.sums = {}
        self.spans = [] if keep_spans else None
        self._step_ann = self._ann = None
        self._name = self._time_to = None
        self._tags = {}                 # kind= and exe= of the open dispatch
        self._t_step = self._t = 0.0
        self._launch = _Launch(self)

    def start(self):
        """Top of ``Engine.step``: opens the step and its first phase."""
        self._step_ann = TraceAnnotation("pt.serve.step")
        self._step_ann.__enter__()
        self._t_step = self._t = time.perf_counter()
        self._open("admit", {}, None)

    def _open(self, name, tags, time_to):
        self._name, self._tags, self._time_to = name, tags, time_to
        self._ann = TraceAnnotation("pt.serve." + name, **tags)
        self._ann.__enter__()

    def pause(self):
        """Close the open phase, if any; what follows until the next phase
        opens is the boundary's remainder. Returns the instant."""
        now = time.perf_counter()
        name = self._name
        if name is not None:
            self._ann.__exit__(None, None, None)
            dt = now - self._t
            sums = self.sums
            sums[name + "_s"] = sums.get(name + "_s", 0.0) + dt
            if self._time_to is not None:
                sums[self._time_to] = sums.get(self._time_to, 0.0) + dt
            if self.spans is not None:
                self.spans.append({"name": "pt.serve." + name,
                                   "t0": self._t, "t1": now, **self._tags})
            self._name = None
        self._t = now
        return now

    def _switch(self, name, tags=None, time_to=None):
        now = self.pause()
        self._open(name, tags or {}, time_to)
        return now

    def admit(self):
        return self._switch("admit")

    def feed(self, kind, time_to, exe):
        return self._switch("feed", {"kind": kind, "exe": exe}, time_to)

    def launch(self):
        """``with clock.launch():`` around the jitted call of the open
        feed, and nothing else."""
        return self._launch

    def wait(self):
        """From the jitted call's return to its outputs on the host: the
        same dispatch as the feed before it."""
        return self._switch("wait", self._tags, self._time_to)

    def emit(self):
        return self._switch("emit")

    def add(self, name, value):
        """A request-level sum taken inside the boundary: rides the same
        flush."""
        self.sums[name] = self.sums.get(name, 0) + value

    def finish(self):
        """End of ``Engine.step`` (also when it raised): closes what is
        open, flushes the sums under one lock and returns the boundary's
        spans (the step first), or None."""
        now = self.pause()
        self._step_ann.__exit__(None, None, None)
        sums, self.sums = self.sums, {}
        sums["step_s"] = now - self._t_step
        with _lock:
            for k, v in sums.items():
                _C[k] += v
        if self.spans is None:
            return None
        spans, self.spans = self.spans, []
        return [{"name": "pt.serve.step", "t0": self._t_step, "t1": now}] \
            + spans


def observe_boundary(queue_depth, active, slots):
    with _lock:
        _C["boundaries"] += 1
        _C["queue_depth_sum"] += queue_depth
        _C["queue_depth_max"] = max(_C["queue_depth_max"], queue_depth)
        _C["active_slot_steps"] += active
        _C["slot_steps"] += slots


def observe_pages(in_use, total):
    with _lock:
        _C["page_boundaries"] += 1
        _C["pages_inuse_sum"] += in_use
        _C["pages_inuse_max"] = max(_C["pages_inuse_max"], in_use)
        _C["pages_total"] = total


def observe_prefill_waste(padded_tokens):
    with _lock:
        _C["prefill_padded_reqs"] += 1
        _C["prefill_padded_tokens"] += padded_tokens
        _C["prefill_padded_max"] = max(_C["prefill_padded_max"],
                                       padded_tokens)


def observe_ttft(seconds, priority=None):
    with _lock:
        _ttft.append(seconds)
        if priority is not None:
            _ttft_cls.setdefault(priority,
                                 deque(maxlen=_MAX_SAMPLES)).append(seconds)


def observe_queue_wait(seconds, outcome):
    """Queue-wait of a request refused from the QUEUE (``outcome`` is
    "shed" or "expired"): the ledger shows how long refused traffic sat
    before the verdict, so shed/expired work is visible in
    ``serving_summary()`` instead of vanishing."""
    with _lock:
        _C[f"{outcome}_queue_wait_s"] += max(0.0, seconds)
        _C[f"{outcome}_queue_waits"] += 1


def recent_ttft_p50(n=256):
    """p50 over the last ``n`` TTFT samples (None when empty) — the cheap
    live estimate the preemption margin derives from, without computing
    the full serving_counters() snapshot every boundary."""
    with _lock:
        if not _ttft:
            return None
        tail = list(_ttft)[-int(n):]
    return float(np.percentile(tail, 50))


def recent_ttft_p99(n=512):
    """p99 over the last ``n`` TTFT samples (None when empty) — the live
    latency gauge the autoscaler compares against its SLO."""
    with _lock:
        if not _ttft:
            return None
        tail = list(_ttft)[-int(n):]
    return float(np.percentile(tail, 99))


def serving_counters():
    """Snapshot of the serving ledger plus derived rates: ttft p50/p99,
    tokens/s over executable time, slot occupancy, mean queue depth."""
    with _lock:
        out = dict(_C)
        ttft = list(_ttft)
        cls_samples = {c: list(v) for c, v in _ttft_cls.items()}
        ad_tokens = dict(_adapter_tokens)
    out["ttft_p50"] = float(np.percentile(ttft, 50)) if ttft else None
    out["ttft_p99"] = float(np.percentile(ttft, 99)) if ttft else None
    for c, v in cls_samples.items():
        out[f"ttft_p50_{c}"] = float(np.percentile(v, 50))
        out[f"ttft_p99_{c}"] = float(np.percentile(v, 99))
    out["shed_queue_wait_mean"] = (
        out["shed_queue_wait_s"] / out["shed_queue_waits"]
        if out["shed_queue_waits"] else 0.0)
    out["expired_queue_wait_mean"] = (
        out["expired_queue_wait_s"] / out["expired_queue_waits"]
        if out["expired_queue_waits"] else 0.0)
    # tokens_out counts prefill-emitted first tokens too, so the rate
    # divides by total executable time (prefill + decode), not decode alone
    exec_t = out["decode_time_s"] + out["prefill_time_s"]
    out["tokens_per_s"] = out["tokens_out"] / exec_t if exec_t > 0 else 0.0
    out["occupancy"] = (out["active_slot_steps"] / out["slot_steps"]
                        if out["slot_steps"] else 0.0)
    out["queue_depth_mean"] = (out["queue_depth_sum"] / out["boundaries"]
                               if out["boundaries"] else 0.0)
    out["page_occupancy"] = (
        out["pages_inuse_sum"] / (out["page_boundaries"] * out["pages_total"])
        if out["page_boundaries"] and out["pages_total"] else 0.0)
    out["prefix_hit_rate"] = (out["prefix_hits"] / out["prefix_lookups"]
                              if out["prefix_lookups"] else 0.0)
    out["prefill_waste_mean"] = (
        out["prefill_padded_tokens"] / out["prefill_padded_reqs"]
        if out["prefill_padded_reqs"] else 0.0)
    # speculative decoding: what fraction of proposed draft tokens the
    # verify pass accepted, and how many tokens ONE dispatch buys on
    # average (draft + verify both count — the honest amortization; the
    # plain engine's equivalent is exactly 1.0)
    out["accept_rate"] = (out["spec_accepted"] / out["spec_proposed"]
                          if out["spec_proposed"] else 0.0)
    spec_disp = out["draft_dispatches"] + out["verify_dispatches"]
    out["tokens_per_dispatch"] = (out["spec_tokens_out"] / spec_disp
                                  if spec_disp else 0.0)
    # many-model serving: per-adapter token counts and shares (fraction of
    # all adapter-attributed tokens, base id 0 included) — the WFQ
    # fairness gauges. Keys appear only for adapters that emitted tokens.
    ad_total = sum(ad_tokens.values())
    for aid, n in sorted(ad_tokens.items()):
        out[f"adapter_tokens_{aid}"] = n
        out[f"adapter_token_share_{aid}"] = (n / ad_total if ad_total
                                             else 0.0)
    return out


def reset_serving_counters():
    global _C
    with _lock:
        _C = _zero()
        _ttft.clear()
        _ttft_cls.clear()
        _adapter_tokens.clear()
        # _mp_info / _adapter_info survive on purpose: they are engine
        # CONFIGURATION (the live rung/degree/capacity labels), not
        # counters — a benchmark resetting counters between rungs must
        # not blank the summary's config labels


_PREFIX_KEYS = ("prefix_lookups", "prefix_hits", "prefix_tokens_reused")


def seed_prefix_counters(snapshot_counters):
    """Counter-lifecycle unification for prefix-cache stats across
    ``load_state_dict(restore_metrics=False)``: the restored engine brings
    its prefix-cache ENTRIES back (they live in the pool snapshot), but
    under restore_metrics=False the hit/reuse counters describing them
    stayed at whatever the live ledger holds — on a fresh respawn that is
    zero, so hit-rate reporting diverged from the recovery ledger (which
    does record the restore). Seed the prefix family from the snapshot
    ONLY when the live family is untouched — a warm engine restoring a
    snapshot (preempt-drain resume on the same process) keeps its own
    live counts exactly like every other serving counter. Returns True
    when seeding happened."""
    with _lock:
        if any(_C[k] for k in _PREFIX_KEYS):
            return False
        for k in _PREFIX_KEYS:
            _C[k] = snapshot_counters.get(k, 0)
        return True


def export_state():
    """Serializable snapshot of the raw ledger (counters + latency ring
    buffers) for ``Engine.state_dict()`` — a restored engine can carry its
    SLO history across a restart instead of reporting from zero."""
    with _lock:
        return {"counters": dict(_C), "ttft": list(_ttft),
                "ttft_cls": {c: list(v) for c, v in _ttft_cls.items()},
                "adapter_tokens": dict(_adapter_tokens)}


def import_state(state):
    """Replace the ledger with an ``export_state()`` snapshot. Unknown
    keys from older snapshots are dropped; keys added since are zeroed."""
    global _C
    with _lock:
        _C = _zero()
        for k, v in state.get("counters", {}).items():
            if k in _C:
                _C[k] = v
        _ttft.clear()
        _ttft.extend(state.get("ttft", ()))
        _ttft_cls.clear()
        for c, v in state.get("ttft_cls", {}).items():
            _ttft_cls[c] = deque(v, maxlen=_MAX_SAMPLES)
        _adapter_tokens.clear()
        for aid, n in state.get("adapter_tokens", {}).items():
            # JSON round-trips stringify int keys; normalize back
            _adapter_tokens[int(aid)] = int(n)


def serving_summary():
    """One-line human-readable serving report."""
    c = serving_counters()
    ttft = ("n/a" if c["ttft_p50"] is None
            else f"{c['ttft_p50'] * 1e3:.1f}/{c['ttft_p99'] * 1e3:.1f}ms")
    paged = ""
    if c["paged_steps"]:
        paged = (f"  pages: {c['page_occupancy'] * 100:.1f}% of "
                 f"{c['pages_total']} used "
                 f"(max {c['pages_inuse_max']})  "
                 f"prefix-hit: {c['prefix_hit_rate'] * 100:.1f}% "
                 f"({c['prefix_tokens_reused']} tok reused)  "
                 f"chunk-interleaved: {c['chunk_steps']}/{c['paged_steps']} "
                 f"steps  cow: {c['cow_copies']}")
    waste = ""
    if c["prefill_padded_reqs"]:
        waste = (f"  prefill-waste: {c['prefill_waste_mean']:.1f} "
                 f"avg/{c['prefill_padded_max']} max pad tok")
    heal = ""
    if any(c[k] for k in ("snapshots", "snapshot_restores", "preempt_drains",
                          "requeued", "replayed", "respawns",
                          "stale_failovers", "rolling_restarts", "dropped",
                          "anomalies_quarantined")):
        heal = (f"  self-heal: {c['snapshots']} snap / "
                f"{c['snapshot_restores']} restore  "
                f"drains: {c['preempt_drains']}  "
                f"requeued/replayed: {c['requeued']}/{c['replayed']}  "
                f"respawns: {c['respawns']} "
                f"({c['stale_failovers']} stale-hb)  "
                f"dropped: {c['dropped']}"
                + (f"  anomalies-quarantined: {c['anomalies_quarantined']}"
                   if c["anomalies_quarantined"] else ""))
    quant = ""
    with _lock:
        qinfo = dict(_quant_info)
    if qinfo:
        drift = (f"  drift-max: {c['quant_logit_drift_max']:.2e}"
                 if c["quant_logit_drift_max"] else "")
        quant = (f"  quant: w={qinfo.get('weight_dtype', '?')} "
                 f"kv={qinfo.get('kv_dtype', '?')}  "
                 f"scales: {c['quant_scale_bytes']}B  "
                 f"kv-bytes/tok: {c['quant_kv_bytes_per_token']}{drift}")
    spec = ""
    if c["verify_dispatches"]:
        spec = (f"  spec: accept: {c['accept_rate'] * 100:.1f}% "
                f"({c['spec_accepted']}/{c['spec_proposed']})  "
                f"tok/dispatch: {c['tokens_per_dispatch']:.2f}  "
                f"draft/verify: {c['draft_dispatches']}/"
                f"{c['verify_dispatches']}")
    mp = ""
    if c["mp_steps"]:
        with _lock:
            info = dict(_mp_info)
        mp = (f"  mp: {info.get('backend', '?')}x{info.get('mp', '?')}  "
              f"wire: {c['mp_wire_bytes'] / 1e6:.2f}MB over "
              f"{c['mp_collectives']} collectives in {c['mp_steps']} "
              f"dispatches  fused-dispatches: {c['mp_fused_dispatches']}")
    disagg = ""
    if any(c[k] for k in ("prefill_handoffs", "transfers", "affinity_hits",
                          "disagg_fallbacks", "role_rebalances")):
        disagg = (f"  disagg: {c['prefill_handoffs']} handoffs / "
                  f"{c['transfers']} transfers "
                  f"({c['transfer_pages']} pages, "
                  f"{c['transfer_bytes'] / 1e6:.2f}MB, "
                  f"{c['transfer_time_s'] * 1e3:.0f}ms)  "
                  f"affinity-hits: {c['affinity_hits']}  "
                  f"fallbacks: {c['disagg_fallbacks']}  "
                  f"role-rebalances: {c['role_rebalances']}")
    slo = ""
    if any(c[k] for k in ("shed", "preempted", "rate_limited", "scale_ups",
                          "scale_downs", "weight_swaps")):
        cls_p99 = "  ".join(
            f"{k[len('ttft_p99_'):]}-p99: {c[k] * 1e3:.1f}ms"
            for k in sorted(c) if k.startswith("ttft_p99_"))
        slo = (f"  slo: {c['shed']} shed "
               f"({c['shed_queue_wait_mean'] * 1e3:.0f}ms avg wait)  "
               f"preempted: {c['preempted']}  "
               f"rate-limited: {c['rate_limited']}  "
               f"scale: +{c['scale_ups']}/-{c['scale_downs']}  "
               f"weight-swaps: {c['weight_swaps']}"
               + (f"  {cls_p99}" if cls_p99 else ""))
    adapters = ""
    with _lock:
        ainfo = dict(_adapter_info)
        ad_tokens = dict(_adapter_tokens)
    if ainfo and (c["adapters_resident"] or c["adapter_loads"]
                  or c["adapter_evicts"] or c["adapter_swaps"]
                  or c["adapter_admit_blocked"]):
        ad_total = sum(ad_tokens.values())
        top = sorted(ad_tokens.items(), key=lambda kv: -kv[1])[:4]
        share = " ".join(
            f"a{aid}:{n / ad_total * 100:.0f}%" for aid, n in top
            if ad_total) if top else ""
        adapters = (f"  adapters: {c['adapters_resident']}/"
                    f"{ainfo.get('slots', '?')} resident "
                    f"(r{ainfo.get('rank', '?')}, "
                    f"{c['adapter_delta_bytes'] / 1e6:.2f}MB delta)  "
                    f"load/evict/swap: {c['adapter_loads']}/"
                    f"{c['adapter_evicts']}/{c['adapter_swaps']}  "
                    f"admit-blocked: {c['adapter_admit_blocked']}"
                    + (f"  tok-share: {share}" if share else ""))
    sdc = ""
    from ..distributed import integrity as _integrity
    s = _integrity.sdc_counters()
    if s["audits"] or s["crc_checks"] or c["transfer_crc_refusals"]:
        sdc = (f"  sdc: audits: {s['audits']} "
               f"({s['audit_failures']} failed)  "
               f"crc: {s['crc_checks']} checked / "
               f"{s['crc_refusals']} refused")
    return (f"requests: {c['submitted']} submitted / {c['completed']} done "
            f"({c['expired']} expired, {c['rejected']} rejected)  "
            f"tokens: {c['tokens_out']}  tokens/s: {c['tokens_per_s']:.1f}  "
            f"ttft p50/p99: {ttft}  occupancy: {c['occupancy'] * 100:.1f}%  "
            f"queue: {c['queue_depth_mean']:.1f} avg/{c['queue_depth_max']} max  "
            f"executables: {c['paged_traces']} paged"
            f"{paged}{quant}{spec}{mp}{adapters}{disagg}{waste}{slo}{heal}"
            f"{sdc}")
