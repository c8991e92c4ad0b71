"""paddle.profiler parity on top of jax.profiler (ref: python/paddle/profiler).

The reference collects host/device events into its own timeline; on TPU the
source of truth is XLA's xplane trace. Profiler here drives
jax.profiler.start_trace/stop_trace (viewable in TensorBoard / Perfetto) and
keeps a host-side RecordEvent timeline exported as chrome tracing JSON.
"""
from __future__ import annotations

import json
import os
import threading
import time
from enum import Enum

import jax

from .xplane import device_time, device_time_summary  # noqa: F401


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    CUSTOM_DEVICE = 2
    TPU = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    """Step-state scheduler (ref profiler/utils.py make_scheduler)."""

    def schedule(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        cycle = closed + ready + record
        if repeat and s >= cycle * repeat:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


_host_events = []
_events_lock = threading.Lock()
_nesting = threading.local()  # per-thread active RecordEvent depth


class RecordEvent:
    """Context/annotation for a named host-side region; also forwards to
    jax.profiler.TraceAnnotation so it appears in the xplane trace.

    Instances are RE-ENTERABLE: each ``begin()`` opens a fresh
    TraceAnnotation onto a per-instance stack (the seed silently reused
    one annotation, so ``begin(); begin()`` corrupted both regions), and
    nested regions — same instance or different — export their per-thread
    nesting depth in the chrome trace (``args.depth``). ``end()`` without
    a matching ``begin()`` raises instead of emitting garbage."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._stack = []        # (t0_ns, TraceAnnotation, depth)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()

    def begin(self):
        ann = jax.profiler.TraceAnnotation(self.name)
        ann.__enter__()
        depth = getattr(_nesting, "depth", 0)
        _nesting.depth = depth + 1
        self._stack.append((time.perf_counter_ns(), ann, depth,
                            threading.get_ident()))

    def end(self):
        if not self._stack:
            raise RuntimeError(
                f"RecordEvent({self.name!r}).end() without a matching "
                f"begin()")
        t0, ann, depth, tid = self._stack.pop()
        if threading.get_ident() == tid:
            # only the beginning thread's nesting counter moves: an end()
            # from another thread must not decrement that thread's depth
            # (and the beginner's counter re-syncs at its next begin/end)
            _nesting.depth = max(0, getattr(_nesting, "depth", 1) - 1)
        ann.__exit__(None, None, None)
        with _events_lock:
            _host_events.append(
                {"name": self.name, "ph": "X", "pid": os.getpid(),
                 "tid": threading.get_ident(),
                 "ts": t0 / 1000.0,
                 "dur": (time.perf_counter_ns() - t0) / 1000.0,
                 "args": {"depth": depth}})


def export_chrome_tracing(dir_name, worker_name=None):
    """Returns an on_trace_ready callback writing chrome tracing JSON."""

    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        path = os.path.join(dir_name, f"{worker_name or 'worker'}.json")
        with _events_lock:
            events = list(_host_events)
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)
        prof._chrome_trace_path = path

    return handler


class Profiler:
    """paddle.profiler.Profiler parity: scheduler-driven trace capture."""

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 trace_dir=None):
        self.scheduler = (make_scheduler(closed=0, ready=0, record=scheduler[1] - scheduler[0],
                                         skip_first=scheduler[0])
                          if isinstance(scheduler, (tuple, list)) else scheduler)
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.trace_dir = trace_dir or "/tmp/paddle_tpu_profile"
        self._step = 0
        self._tracing = False
        self._step_times = []
        self._t_last = None

    def start(self):
        self._t_last = time.perf_counter()
        if not self.timer_only:
            state = self.scheduler(self._step) if self.scheduler else ProfilerState.RECORD
            if state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
                self._start_trace()

    def _start_trace(self):
        if not self._tracing:
            os.makedirs(self.trace_dir, exist_ok=True)
            try:
                jax.profiler.start_trace(self.trace_dir)
                self._tracing = True
            except Exception:
                self._tracing = False

    def _stop_trace(self):
        if self._tracing:
            try:
                jax.profiler.stop_trace()
            finally:
                self._tracing = False

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._t_last is not None:
            self._step_times.append(now - self._t_last)
        self._t_last = now
        self._step += 1
        if self.timer_only or self.scheduler is None:
            return
        state = self.scheduler(self._step)
        if state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._start_trace()
        else:
            if self._tracing:
                self._stop_trace()
                if state == ProfilerState.CLOSED and self.on_trace_ready:
                    self.on_trace_ready(self)
        if state == ProfilerState.RECORD_AND_RETURN and self.on_trace_ready:
            self.on_trace_ready(self)

    def stop(self):
        self._stop_trace()
        if self.on_trace_ready and not self.timer_only:
            self.on_trace_ready(self)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        if not self._step_times:
            return "no steps recorded"
        import numpy as np
        ts = np.asarray(self._step_times) * 1e3
        return (f"steps: {len(ts)}  avg: {ts.mean():.2f}ms  p50: "
                f"{np.percentile(ts, 50):.2f}ms  p99: {np.percentile(ts, 99):.2f}ms")


# -- eager dispatch-cache counters -------------------------------------------
# The jit-cached eager dispatch (dispatch.py) counts every apply() call,
# LRU hit/miss, actual XLA (re)trace, and uncacheable fallback. hit_rate()
# is the steady-state fraction of cached dispatches that re-used compiled
# code — the first metric to look at when the dygraph path is slow.

def dispatch_counters():
    """Snapshot of the eager dispatch-cache counters as a dict, plus the
    derived steady-state `hit_rate` and current `cache_entries`. (Thin
    view over the observability registry's "dispatch" family — same dict,
    also reachable via ``observability.snapshot()`` / Prometheus.)"""
    from ..observability import collect
    return collect("dispatch")


def reset_dispatch_counters():
    from ..dispatch import reset_cache_stats
    reset_cache_stats()


def dispatch_cache_summary():
    """One-line human-readable dispatch-cache report."""
    c = dispatch_counters()
    return (f"dispatches: {c['dispatches']}  cached: {c['cached_calls']}  "
            f"traces: {c['traces']}  fallbacks: {c['fallbacks']}  "
            f"hit-rate: {c['hit_rate'] * 100:.1f}%  "
            f"entries: {c['cache_entries']}")


# -- gradient-communication counters ----------------------------------------
# The explicit grad-comm layer (distributed/grad_comm.py) has a static
# collective schedule per compiled TrainStep; every executed step records its
# wire bytes (reduce vs gather, by dtype), collective count, bucket count and
# bucket fill here. The first thing to look at when a DP step is
# communication-bound — and the evidence hook for the reduce-scatter and
# quantized-reduce wins.

def comm_counters():
    """Snapshot of the gradient-communication counters: reduce_bytes (+ by
    dtype), gather_bytes, collectives, buckets, bucket_fill, steps — plus
    the per-axis `backend` label ({'dp': 'ring'|'fused'}) and
    `fused_dispatches` (Pallas kernel launches of the fused backend), so
    counter gates can assert which backend actually ran. (Thin view over
    the registry's "comm" family.)"""
    from ..observability import collect
    return collect("comm")


def reset_comm_counters():
    from ..distributed import grad_comm
    grad_comm.reset_comm_counters()


def comm_summary():
    """One-line human-readable gradient-communication report. The backend
    label covers every axis with an explicit schedule this process ran —
    dp (grad_comm) plus the pp pipeline ledger's label when pipelined
    steps were recorded."""
    c = comm_counters()
    by = " ".join(f"{k}:{v / 1e6:.2f}MB"
                  for k, v in sorted(c["reduce_bytes_by_dtype"].items()))
    label = dict(c["backend"])
    label.update(pp_comm_counters()["backend"])
    backend = ",".join(f"{a}={b}" for a, b in sorted(label.items())) \
        or "gspmd"
    return (f"steps: {c['steps']}  backend: {backend}  "
            f"collectives: {c['collectives']}  "
            f"reduce: {c['reduce_bytes'] / 1e6:.2f}MB ({by})  "
            f"gather: {c['gather_bytes'] / 1e6:.2f}MB  "
            f"buckets: {c['buckets']}  fill: {c['bucket_fill'] * 100:.1f}%  "
            f"fused-dispatches: {c['fused_dispatches']}")


# -- tensor-parallel (mp-axis) communication counters ------------------------
# The explicit mp schedule (distributed/tp_overlap.py; FLAGS_sequence_parallel
# / FLAGS_mp_overlap) has a static per-step collective ledger: reduce-scatter
# and all-gather wire bytes, collective counts, ring ppermute hops, and the
# inter-block activation residency per device. Recorded per executed step —
# the evidence hook for "per-block mp all-reduces replaced by RS+AG" and the
# 1/mp activation claim.


def mp_comm_counters():
    """Snapshot of the mp-axis schedule counters: rs_bytes, ag_bytes,
    wire_bytes, collectives, ppermute_hops, activation_bytes, steps — plus
    the per-axis `backend` label ({'mp': 'rsag'|'ring'|'fused'}) and
    `fused_dispatches` (Pallas GEMM+collective kernel launches per the
    static forward schedule), so counter gates can assert which backend
    actually ran. (Thin view over the registry's "mp_comm" family.)"""
    from ..observability import collect
    return collect("mp_comm")


def reset_mp_comm_counters():
    from ..distributed import tp_overlap
    tp_overlap.reset_mp_counters()


def mp_comm_summary():
    """One-line human-readable mp-axis communication report (the backend
    label also names the pp axis when pipelined steps were recorded — the
    two explicit model-parallel schedules compose in one region)."""
    c = mp_comm_counters()
    label = dict(c["backend"])
    label.update(pp_comm_counters()["backend"])
    backend = ",".join(f"{a}={b}" for a, b in sorted(label.items())) \
        or "gspmd"
    return (f"steps: {c['steps']}  backend: {backend}  "
            f"collectives: {c['collectives']}  "
            f"rs: {c['rs_bytes'] / 1e6:.2f}MB  "
            f"ag: {c['ag_bytes'] / 1e6:.2f}MB  "
            f"ppermute-hops: {c['ppermute_hops']}  "
            f"fused-dispatches: {c['fused_dispatches']}  "
            f"act/block: {c['activation_bytes'] / 1e6:.3f}MB")


# -- pipeline-parallel (pp-axis) communication counters ----------------------
# The explicit pp schedule (distributed/pipeline.py ring/fused backends;
# FLAGS_comm_backend='pp=...') has a static per-step boundary ledger:
# boundary activation/cotangent wire bytes, explicit ppermute hops, fused
# boundary-kernel dispatches and the schedule's bubble-fraction estimate.
# Recorded per executed HybridTrainStep — the evidence hook for "boundary
# sends overlapped into the next tick's stage compute" and the fused
# last-GEMM RDMA epilogue.


def pp_comm_counters():
    """Snapshot of the pp-axis schedule counters: boundary_bytes,
    ppermute_hops, fused_dispatches, steps, plus the schedule shape
    (schedule, stages, microbatches, bubble_fraction — the idle-slot
    estimate, gpipe (S-1)/(M+S-1), 1f1b (2S-2)/(M+2S-2)) and the per-axis
    `backend` label ({'pp': 'gspmd'|'ring'|'fused'}), so counter gates can
    assert which backend actually ran. (Thin view over the registry's
    "pp_comm" family.)"""
    from ..observability import collect
    return collect("pp_comm")


def reset_pp_comm_counters():
    from ..distributed import pipeline
    pipeline.reset_pp_counters()


def pp_comm_summary():
    """One-line human-readable pp-axis communication report."""
    c = pp_comm_counters()
    backend = ",".join(f"{a}={b}" for a, b in sorted(c["backend"].items())) \
        or "gspmd"
    return (f"steps: {c['steps']}  backend: {backend}  "
            f"schedule: {c['schedule'] or '-'}  "
            f"stages: {c['stages']}  microbatches: {c['microbatches']}  "
            f"boundary: {c['boundary_bytes'] / 1e6:.2f}MB  "
            f"ppermute-hops: {c['ppermute_hops']}  "
            f"fused-dispatches: {c['fused_dispatches']}  "
            f"bubble: {c['bubble_fraction'] * 100:.1f}%")


# -- fault-tolerance counters -------------------------------------------------
# The compiled anomaly guard (jit/train_step.py, FLAGS_anomaly_policy), the
# hardened CheckpointManager (incubate/checkpoint.py) and the chaos harness
# (utils/fault_injection.py) each keep a ledger. `host_syncs` is the audit
# trail for the guard's zero-extra-sync contract: one combined (loss,
# step_ok...) fetch per UPDATE step — host_syncs == steps at
# accumulate_steps=1, and steps/k under accumulation (micro flags ride to
# the fire boundary in the same fetch). Anything above that means a sync
# snuck in.


def fault_counters():
    """Snapshot of the fault-tolerance counters: anomaly guard (steps,
    host_syncs, bad_steps, skipped_updates, rollbacks), checkpoint manager
    (saves, save_retries, quarantined, restore_fallbacks, preempt_saves)
    and injected-fault stats. (Thin view over the registry's "fault"
    family.)"""
    from ..observability import collect
    return collect("fault")


def reset_fault_counters():
    from ..jit import train_step as _ts
    from ..incubate import checkpoint as _ck
    _ts.reset_anomaly_counters()
    _ck.reset_ckpt_counters()


def fault_summary():
    """One-line human-readable fault-tolerance report (an ``sdc:``
    segment appears only when the integrity sentinel did any work)."""
    c = fault_counters()
    a, k = c["anomaly"], c["checkpoint"]
    line = (f"steps: {a['steps']}  host-syncs: {a['host_syncs']}  "
            f"bad: {a['bad_steps']}  skipped: {a['skipped_updates']}  "
            f"rollbacks: {a['rollbacks']}  saves: {k['saves']}  "
            f"retries: {k['save_retries']}  quarantined: {k['quarantined']}  "
            f"preempt-saves: {k['preempt_saves']}")
    from ..distributed import integrity as _integrity
    s = _integrity.sdc_counters()
    if any(s.values()):
        line += (f"  sdc: checks={s['fingerprint_checks']} "
                 f"mismatches={s['fingerprint_mismatches']} "
                 f"repairs={s['repairs']} "
                 f"redispatches={s['repair_redispatches']} "
                 f"scrubs={s['scrubs']} rot={s['rot_found']} "
                 f"quarantined={s['quarantined_ranks']}")
    return line


# -- serving counters ---------------------------------------------------------
# The continuous-batching engine (serving/engine.py) ledgers every request,
# prefill chunk, decode iteration and token. The trace counters
# (paged_traces/copy_traces for the fused step and the CoW page copy) are
# the no-recompile audit trail: each jitted body counts only when actually
# traced, so after warmup the counts freeze — joins, evicts, chunked
# admissions, CoW remaps and sampling-param changes must not move them (and
# an Engine RESTORED from a snapshot re-dispatches the warm executables, so
# a restore must not move them either). TTFT/token-latency percentiles,
# tokens/s, slot occupancy and queue depth are the serving SLO surface,
# beside page occupancy, prefix-cache hit rate / tokens reused,
# chunk-interleave counters and per-prefill padded-token waste. The
# self-healing runtime
# (engine snapshots + ServingSupervisor) adds the recovery ledger:
# snapshots/snapshot_restores, preempt_drains, requeued/replayed,
# respawns, stale_failovers, rolling_restarts — and "dropped", which must
# stay 0 through any kill/preemption/rolling-restart story.


def serving_counters():
    """Snapshot of the serving-engine counters: request lifecycle
    (submitted/admitted/completed/expired/rejected), executable calls and
    traces, tokens_out, ttft_p50/p99, tokens_per_s, occupancy, queue depth
    — plus the paged-KV ledger (page_occupancy, prefix_hit_rate,
    prefix_tokens_reused, chunk_steps, cow_copies, prefill_waste_mean).

    The phase clock of ``Engine.step`` (always on): ``step_s`` over
    ``boundaries`` is the mean boundary, and ``admit_s`` / ``feed_s`` /
    ``wait_s`` / ``emit_s`` its disjoint phases (with a small remainder
    they sum to ``step_s``); ``launch_s`` is the part of ``feed_s`` inside
    the jitted calls themselves. ``decode_time_s`` / ``prefill_time_s`` are
    feed + wait of the decode-side and of the chunk / prefill dispatches,
    each ended by its outputs reaching the host; ``admit_queue_wait_s`` /
    ``admit_queue_waits`` is submit to admission of admitted requests and
    ``prefill_span_s`` / ``first_tokens`` admission to the first token.
    ``paged_uploads`` / ``paged_fetches`` count the host-to-device arrays
    the engine's dispatches sent and the device-to-host arrays they
    fetched, where they are sent and fetched: one of each a paged step
    (``paged_steps``), the slot operands riding one buffer and the small
    outputs one (serving/operands.py); two more uploads a step with a
    quantised pool's scale tables.
    The same phases are ``jax.profiler.TraceAnnotation`` spans
    (``pt.serve.step`` around ``pt.serve.admit | feed | wait | emit``, a
    dispatch's feed and wait with ``kind=chunk|decode|draft|verify`` and
    ``exe=`` the name its executable runs under, ``pt.serve.launch`` with
    both nested in the feed around the jitted call; ``device_time`` reads
    a profile by those names; the trainers' dispatch is
    ``pt.train.step``): see them in a
    ``jax.profiler.start_trace`` session, on the device trace's clock, or
    on the ``boundaries`` thread of ``Engine.export_trace()`` with
    ``FLAGS_serving_trace`` on. (Thin view over the registry's "serving"
    family.)"""
    from ..observability import collect
    return collect("serving")


def reset_serving_counters():
    from ..serving import metrics
    metrics.reset_serving_counters()


def serving_summary():
    """One-line human-readable serving report."""
    from ..serving import metrics
    return metrics.serving_summary()


def recovery_counters():
    """Self-healing subset of the serving ledger: engine snapshots taken /
    restored, preemption drains, requests requeued / replayed, replica
    respawns, stale-heartbeat failovers, rolling restarts, and dropped
    (the invariant: 0). (Thin view over the registry's "recovery"
    family.)"""
    from ..observability import collect
    return collect("recovery")


def elastic_counters():
    """Topology-elastic ledger: mesh shrinks/grows/reforms and snapshot
    restores the ElasticMeshSupervisor performed, resume latency, steps
    re-executed after a restore, live active-dp/world/failed-ranks gauges,
    plus the reshard-on-load counters (checkpoints loaded across a
    topology change, leaves moved, rejected mismatched loads). (Thin view
    over the registry's "elastic" family.)"""
    from ..observability import collect
    return collect("elastic")


def reset_elastic_counters():
    from ..distributed import elastic as _el
    from ..distributed import topology as _topo
    _el.reset_elastic_counters()
    _topo.reset_reshard_counters()


def elastic_summary():
    """One-line human-readable topology-elastic report (training mesh
    reforms plus, when a topology-elastic serving fleet ran, the serving
    group-reform segment)."""
    c = elastic_counters()
    serving = ""
    if c.get("group_reforms") or c.get("degraded_groups"):
        serving = (f"  serving: {c['group_reforms']} group-reforms "
                   f"({c['grow_backs']} grow-backs)  "
                   f"degraded-groups: {c['degraded_groups']}  "
                   f"chips-lost: {c['serving_chips_lost']}  "
                   f"reform: {c['reform_latency_s_last'] * 1e3:.0f}ms")
    return (f"dp: {c['active_dp']}/{c['world_size']}  "
            f"failed-ranks: {c['failed_ranks']}  "
            f"shrinks: {c['shrinks']}  grows: {c['grows']}  "
            f"restores: {c['elastic_restores']}  "
            f"resharded-loads: {c['resharded_loads']} "
            f"({c['resharded_leaves']} leaves)  "
            f"steps-lost: {c['steps_lost']}  "
            f"resume: {c['resume_latency_s_last'] * 1e3:.0f}ms" + serving)


def benchmark():
    """Step-timer handle (ref profiler.utils.benchmark)."""
    return _Benchmark()


class _Benchmark:
    def __init__(self):
        self._times = []
        self._t = None

    def begin(self):
        self._t = time.perf_counter()

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._t is not None:
            self._times.append(now - self._t)
        self._t = now

    def end(self):
        pass

    def step_info(self, unit="ms"):
        import numpy as np
        if not self._times:
            return "n/a"
        return f"avg {np.mean(self._times) * 1e3:.3f} ms/step"


def load_profiler_result(path):
    with open(path) as f:
        return json.load(f)


class SortedKeys:
    """ref: profiler/profiler_statistic.py SortedKeys enum."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView:
    """ref: profiler SummaryView enum."""
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def export_protobuf(dir_name, worker_name=None):
    """The jax profiler's native artifact is xplane protobuf; exporting
    chrome tracing also materializes the .xplane.pb files under dir_name."""
    return export_chrome_tracing(dir_name, worker_name)
