"""Live step telemetry for training loops.

With ``FLAGS_step_telemetry`` on, ``jit.TrainStep`` and
``models.gpt_hybrid.HybridTrainStep`` record a sampled per-step record
(every ``FLAGS_step_telemetry_every`` steps): wall time split into
dispatch (async jit call) and host-sync (block until the loss is real),
achieved MFU from the model's STATIC FLOP count
(observability/flops.py — the same estimator the bench uses, so live and
offline MFU cannot diverge), wire bytes from the static comm-schedule
records (grad_comm / tp_overlap), and device-memory watermarks via
``jax.live_arrays`` / per-device ``memory_stats``.

Wall time is averaged over the WINDOW since the previous sample (the
sampled step's own sync would otherwise absorb the drained async queue of
the unsampled steps in between and over-read), so sampling is cheap while
the number stays honest.

An EWMA regression sentinel tracks the rolling step-time baseline and
logs a warning whenever a sampled step drifts more than
``FLAGS_step_time_drift_pct`` above it — the "this run just got slower"
tripwire for long pretraining jobs.

Everything is host-side timing around the already-existing jit dispatch:
telemetry on/off never adds a traced operand or a retrace, and when off
the cost is one dict lookup per step.
"""
from __future__ import annotations

import logging
import threading
import time
from collections import deque

_log = logging.getLogger("paddle_tpu.observability")

_lock = threading.Lock()
_records = deque(maxlen=4096)

_EWMA_ALPHA = 0.2
_WARMUP = 2  # samples ignored by the sentinel (compile + cache warm)


def _zero():
    # "last_*" fields are LATEST-SAMPLE values: with several live train
    # steps in one process they name whichever model sampled last (see
    # last_tag); per-model history is records() filtered by tag
    return {"steps_seen": 0, "sampled": 0, "drift_alerts": 0,
            "last_tag": None, "wall_ema_s": None, "last_wall_s": None,
            "last_dispatch_s": None, "last_sync_s": None,
            "last_mfu": None, "last_tokens_per_s": None,
            "wire_bytes_per_step": 0, "mem_bytes": 0, "mem_peak_bytes": 0,
            "flops_per_step": 0}


_S = _zero()


class _Sentinel:
    """EWMA baseline + warmup counter for the drift check. PER SAMPLER
    (each TrainStep owns one): a process sweeping several models must not
    compare one model's step time against another's baseline, nor let a
    later model's compile step burn the first one's warmup allowance."""

    __slots__ = ("ema", "n")

    def __init__(self):
        self.ema = None
        self.n = 0


_default_sentinel = _Sentinel()   # direct observe() callers (tests, tools)


def enabled():
    from ..flags import _FLAGS
    return bool(_FLAGS.get("FLAGS_step_telemetry", False))


def sample_every():
    from ..flags import _FLAGS
    try:
        return max(1, int(_FLAGS.get("FLAGS_step_telemetry_every", 8)))
    except (TypeError, ValueError):
        return 8


def should_sample(step_idx):
    """One cheap check per step: False when telemetry is off or this step
    is not on the sampling cadence."""
    if not enabled():
        return False
    with _lock:
        _S["steps_seen"] += 1
    return step_idx % sample_every() == 0


def _drift_pct():
    from ..flags import _FLAGS
    try:
        return float(_FLAGS.get("FLAGS_step_time_drift_pct", 25.0))
    except (TypeError, ValueError):
        return 25.0


def device_mem_bytes():
    """Best-effort device-memory watermark: live jax.Array bytes, plus the
    backend allocator's peak when it exposes memory_stats (TPU)."""
    live = peak = 0
    try:
        import jax
        live = int(sum(getattr(a, "nbytes", 0) for a in jax.live_arrays()))
        for d in jax.local_devices():
            stats = getattr(d, "memory_stats", None)
            st = stats() if callable(stats) else None
            if st:
                peak += int(st.get("peak_bytes_in_use",
                                   st.get("bytes_in_use", 0)))
    except Exception:  # noqa: BLE001 — telemetry must never kill a step
        pass
    return live, peak


def observe(tag, step, wall_s, dispatch_s=None, sync_s=None, tokens=None,
            flops=None, wire_bytes=None, peak_flops=None, window=1,
            sentinel=None):
    """Record one sampled step. ``wall_s`` is the per-step average over
    the ``window`` steps since the previous sample. ``sentinel`` scopes
    the drift baseline (a ``StepSampler`` passes its own; direct callers
    share the module default). Returns the record."""
    from .flops import mfu as _mfu
    mem_live, mem_peak = device_mem_bytes()
    rec = {
        "tag": str(tag), "step": int(step), "wall_s": float(wall_s),
        "dispatch_s": None if dispatch_s is None else float(dispatch_s),
        "sync_s": None if sync_s is None else float(sync_s),
        "tokens": None if tokens is None else int(tokens),
        "flops": None if flops is None else float(flops),
        "wire_bytes": None if wire_bytes is None else int(wire_bytes),
        "mem_bytes": mem_live, "mem_peak_bytes": mem_peak,
        "window": int(window), "t": time.time(),
    }
    rec["tokens_per_s"] = (tokens / wall_s if tokens and wall_s > 0
                           else None)
    rec["mfu"] = _mfu(flops, wall_s, peak_flops)
    sb = _default_sentinel if sentinel is None else sentinel
    drift = None
    with _lock:
        _records.append(rec)
        _S["sampled"] += 1
        _S["last_tag"] = rec["tag"]
        _S["last_wall_s"] = rec["wall_s"]
        _S["last_dispatch_s"] = rec["dispatch_s"]
        _S["last_sync_s"] = rec["sync_s"]
        _S["last_mfu"] = rec["mfu"]
        _S["last_tokens_per_s"] = rec["tokens_per_s"]
        _S["mem_bytes"] = mem_live
        _S["mem_peak_bytes"] = max(_S["mem_peak_bytes"], mem_peak, mem_live)
        if wire_bytes is not None:
            _S["wire_bytes_per_step"] = int(wire_bytes)
        if flops is not None:
            _S["flops_per_step"] = float(flops)
        sb.n += 1
        pct = _drift_pct()
        if sb.n <= _WARMUP or sb.ema is None:
            # compile / first-dispatch samples would poison the baseline
            sb.ema = rec["wall_s"] if sb.n >= _WARMUP else None
        else:
            if pct > 0 and rec["wall_s"] > sb.ema * (1.0 + pct / 100.0):
                _S["drift_alerts"] += 1
                drift = (rec["wall_s"], sb.ema, pct)
            sb.ema = (_EWMA_ALPHA * rec["wall_s"]
                      + (1.0 - _EWMA_ALPHA) * sb.ema)
        _S["wall_ema_s"] = rec["wall_ema_s"] = sb.ema
    if drift is not None:
        w, ema, pct = drift
        _log.warning(
            "step-time regression: %s step %d took %.1fms, %.0f%% over the "
            "rolling baseline %.1fms (threshold %.0f%%)",
            tag, step, w * 1e3, (w / ema - 1.0) * 100.0, ema * 1e3, pct)
    return rec


def records():
    with _lock:
        return [dict(r) for r in _records]


def step_counters():
    """Snapshot of the live-step ledger (registry family "step")."""
    with _lock:
        return dict(_S)


def reset_step_telemetry():
    global _S, _default_sentinel
    with _lock:
        _S = _zero()
        _records.clear()
        _default_sentinel = _Sentinel()


def step_summary():
    """One-line human-readable live-step report."""
    c = step_counters()
    if not c["sampled"]:
        return "no sampled steps"
    fmt = lambda v, s=1e3, u="ms": ("n/a" if v is None  # noqa: E731
                                    else f"{v * s:.1f}{u}")
    mfu = "n/a" if c["last_mfu"] is None else f"{c['last_mfu'] * 100:.1f}%"
    tag = f" [{c['last_tag']}]" if c["last_tag"] else ""
    return (f"sampled: {c['sampled']}/{c['steps_seen']} steps{tag}  "
            f"wall: {fmt(c['last_wall_s'])} (ema {fmt(c['wall_ema_s'])})  "
            f"dispatch/sync: {fmt(c['last_dispatch_s'])}/"
            f"{fmt(c['last_sync_s'])}  mfu: {mfu}  "
            f"wire: {c['wire_bytes_per_step'] / 1e6:.2f}MB/step  "
            f"mem: {c['mem_bytes'] / 1e6:.0f}MB "
            f"(peak {c['mem_peak_bytes'] / 1e6:.0f}MB)  "
            f"drift-alerts: {c['drift_alerts']}")


# -- call-site helper ---------------------------------------------------------

class StepSampler:
    """The per-TrainStep host timer: owns the inter-sample window anchor
    so ``wall_s`` averages over unsampled steps too. Zero state when
    telemetry is off; both TrainStep flavors drive it identically::

        t0 = self._tel.begin(self._step)     # None when not sampling
        out = jitted(...)                     # async dispatch
        self._tel.end(t0, self._step, loss, tokens=..., flops=..., ...)
    """

    def __init__(self, tag):
        self.tag = tag
        self._anchor = None       # perf_counter at last sample end
        self._anchor_step = None
        self._peak = False        # False = not yet probed (None is valid)
        self._sentinel = _Sentinel()   # per-model drift baseline
        # every TrainStep flavor owns a sampler, so constructing one is
        # the training runtime's chokepoint for FLAGS_metrics_port (the
        # serving runtime's is Engine.__init__): bring the Prometheus
        # endpoint up when asked, no-op at the default 0
        from .prometheus import start_from_flags
        start_from_flags()

    def begin(self, step_idx):
        if not should_sample(step_idx):
            return None
        return time.perf_counter()

    def end(self, t0, step_idx, sync_arrays, tokens=None, flops=None,
            wire_bytes=None, peak_flops=None):
        if t0 is None:
            return None
        t1 = time.perf_counter()
        try:
            import jax
            jax.block_until_ready(sync_arrays)
        except Exception:  # noqa: BLE001
            pass
        t2 = time.perf_counter()
        if self._anchor is not None and step_idx > self._anchor_step:
            window = step_idx - self._anchor_step
            wall = (t2 - self._anchor) / window
        else:
            window = 1
            wall = t2 - t0
        self._anchor = t2
        self._anchor_step = step_idx
        if peak_flops is None:
            if self._peak is False:
                self._peak = default_peak_flops()
            peak_flops = self._peak
        return observe(self.tag, step_idx, wall, dispatch_s=t1 - t0,
                       sync_s=t2 - t1, tokens=tokens, flops=flops,
                       wire_bytes=wire_bytes, peak_flops=peak_flops,
                       window=window, sentinel=self._sentinel)


def default_peak_flops():
    """Per-process peak FLOP/s: per-chip bf16 peak x local device count.
    None (no MFU) off-TPU — a CPU rate over a TPU peak is not a number."""
    import jax
    from .flops import peak_flops_bf16
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return None
    return peak_flops_bf16(devs[0].device_kind) * len(devs)
