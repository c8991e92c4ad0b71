"""Unified telemetry layer (paddle_tpu.observability).

Gates:
  * typed registry (counter/gauge/histogram, namespaced snapshot/delta);
  * the six counter families as registry collectors, with
    `profiler.*_counters()` thin views BITWISE-compatible with the
    pre-registry dicts;
  * RecordEvent re-entry + nesting depth in the exported chrome trace
    (satellite: the seed silently reused one TraceAnnotation);
  * Prometheus text exposition (render, parse, live endpoint);
  * live step telemetry: sampled records with dispatch/sync split and
    MFU from the shared FLOP estimator; telemetry on/off is bitwise on
    the loss trajectory and adds no retraces; EWMA drift sentinel;
  * serving metrics ledger under concurrent writers/readers (satellite:
    supervisor router/heartbeat threads read while step() bumps);
  * the FLOP estimator single-source contract (bench.py and
    tools_mfu_sweep.py consume observability.flops).
"""
import json
import threading
import time
from urllib.request import urlopen

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.observability import prometheus, step_telemetry
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.gpt_hybrid import HybridTrainStep

CFG = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
                max_seq_len=64, dropout=0.0, use_flash=False,
                compute_dtype="float32", remat=False)


@pytest.fixture(autouse=True)
def _telemetry_off():
    yield
    paddle.set_flags({"FLAGS_step_telemetry": False,
                      "FLAGS_step_telemetry_every": 8,
                      "FLAGS_step_time_drift_pct": 25.0})


# ---------------------------------------------------------------------------
# registry


def test_registry_typed_metrics():
    r = obs.MetricsRegistry()
    c = r.counter("t.requests")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert r.counter("t.requests") is c          # get-or-create

    g = r.gauge("t.depth")
    g.set(7)
    assert g.value == 7
    r.gauge("t.live", fn=lambda: 42)             # callable-backed
    h = r.histogram("t.lat")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.count == 4 and h.sum == 10.0
    assert h.percentile(50) == 2.5

    snap = r.snapshot()
    assert snap["t.requests"] == 5
    assert snap["t.depth"] == 7
    assert snap["t.live"] == 42
    assert snap["t.lat.count"] == 4
    assert "t.lat.p99" in snap

    with pytest.raises(TypeError):
        r.gauge("t.requests")                    # type conflict


def test_registry_snapshot_delta():
    r = obs.MetricsRegistry()
    r.register_family("fam", lambda: {"a": 1, "nested": {"b": 2.5},
                                      "label": "x"})
    s0 = r.snapshot()
    assert s0["fam.a"] == 1
    assert s0["fam.nested.b"] == 2.5
    assert s0["fam.label"] == "x"                # non-numeric kept
    r.counter("c").inc(3)
    d = r.delta(s0)
    assert d["c"] == 3                            # new key diffs against 0
    assert d["fam.a"] == 0
    assert "fam.label" not in d                   # non-numeric skipped


def test_registry_broken_family_isolated():
    r = obs.MetricsRegistry()
    r.register_family("bad", lambda: 1 / 0)
    r.register_family("good", lambda: {"x": 1})
    snap = r.snapshot()
    assert snap["good.x"] == 1
    assert "bad.collect_error" in snap


def test_profiler_counters_are_registry_views():
    """The thin-view contract: profiler.*_counters() == the registry's
    family collect, and both carry the pre-registry keys."""
    pairs = [
        (profiler.dispatch_counters, "dispatch", "hit_rate"),
        (profiler.comm_counters, "comm", "reduce_bytes"),
        (profiler.mp_comm_counters, "mp_comm", "rs_bytes"),
        (profiler.fault_counters, "fault", "anomaly"),
        (profiler.serving_counters, "serving", "submitted"),
        (profiler.recovery_counters, "recovery", "dropped"),
    ]
    for fn, fam, key in pairs:
        via_profiler = fn()
        via_registry = obs.collect(fam)
        assert via_profiler == via_registry, fam
        assert key in via_profiler, fam
    flat = obs.snapshot()
    assert "serving.submitted" in flat
    assert "dispatch.hit_rate" in flat
    assert "step.sampled" in flat


# ---------------------------------------------------------------------------
# RecordEvent re-entry + nesting (satellite)


def test_record_event_reenterable_and_nested():
    from paddle_tpu.profiler import _host_events, _events_lock
    with _events_lock:
        n0 = len(_host_events)
    outer = profiler.RecordEvent("outer")
    inner = profiler.RecordEvent("inner")
    outer.begin()
    inner.begin()
    inner.begin()          # same instance again: re-enter, not reuse
    inner.end()
    inner.end()
    outer.end()
    with _events_lock:
        evs = _host_events[n0:]
    assert [e["name"] for e in evs] == ["inner", "inner", "outer"]
    # depths: outer opened at 0; the two inner begins at depth 1 and 2
    # (events append at END, innermost first)
    assert [e["args"]["depth"] for e in evs] == [2, 1, 0]
    # durations nest: each inner event is contained in outer's window
    o = evs[2]
    for e in evs[:2]:
        assert e["ts"] >= o["ts"]
        assert e["ts"] + e["dur"] <= o["ts"] + o["dur"] + 1e-3


def test_record_event_unbalanced_end_raises():
    ev = profiler.RecordEvent("x")
    with pytest.raises(RuntimeError, match="without a matching begin"):
        ev.end()
    ev.begin()
    ev.end()
    with pytest.raises(RuntimeError):
        ev.end()


# ---------------------------------------------------------------------------
# Prometheus


def test_prometheus_render_and_parse():
    text = prometheus.render({"fam.count": 3, "fam.rate": 0.5,
                              "fam.flag": True, "fam.label": "skip-me",
                              "fam.none": None})
    parsed = prometheus.parse(text)
    assert parsed["paddle_tpu_fam_count"] == 3
    assert parsed["paddle_tpu_fam_rate"] == 0.5
    assert parsed["paddle_tpu_fam_flag"] == 1
    assert not any("label" in k or "none" in k for k in parsed)
    with pytest.raises(ValueError):
        prometheus.parse("not a metric line at all")


def test_prometheus_endpoint_serves_registry():
    srv = obs.start_metrics_server(port=0)
    try:
        assert obs.start_metrics_server(port=0) is srv   # idempotent
        text = urlopen(srv.url, timeout=10).read().decode()
        parsed = prometheus.parse(text)
        for fam in ("dispatch", "serving", "comm", "mp_comm", "fault",
                    "recovery", "step"):
            assert any(k.startswith(f"paddle_tpu_{fam}_") for k in parsed), \
                f"family {fam} missing"
    finally:
        obs.stop_metrics_server()


def test_prometheus_off_by_default():
    assert paddle.get_flags("FLAGS_metrics_port")["FLAGS_metrics_port"] == 0
    assert prometheus.start_from_flags() is None


# ---------------------------------------------------------------------------
# step telemetry


def _train_loop(steps, seed=0):
    paddle.seed(seed)
    opt = paddle.optimizer.AdamW(1e-3)
    step = HybridTrainStep(CFG, opt)
    ids = jax.random.randint(jax.random.key(0), (2, 16), 0,
                             CFG.vocab_size, jnp.int32)
    losses = [float(jax.device_get(step(ids))) for _ in range(steps)]
    return step, losses


def test_step_telemetry_sampled_records():
    paddle.set_flags({"FLAGS_step_telemetry": True,
                      "FLAGS_step_telemetry_every": 1})
    obs.reset_step_telemetry()
    _train_loop(4)
    c = obs.step_counters()
    assert c["sampled"] == 4
    assert c["steps_seen"] == 4
    assert c["last_wall_s"] > 0
    assert c["last_dispatch_s"] is not None and c["last_sync_s"] is not None
    # MFU from the SHARED estimator (bench formula) over the static config
    from paddle_tpu.observability.flops import train_step_flops
    flops, _ = train_step_flops(CFG, 2, 16)
    assert c["flops_per_step"] == flops
    # no MFU off-TPU: a CPU rate is never divided by a TPU peak
    assert c["last_mfu"] is None
    recs = step_telemetry.records()
    assert len(recs) == 4
    assert recs[-1]["tokens"] == 2 * 16
    assert recs[-1]["mem_bytes"] > 0
    assert "mfu" in obs.step_summary() or "sampled" in obs.step_summary()


def test_step_telemetry_sampling_cadence():
    paddle.set_flags({"FLAGS_step_telemetry": True,
                      "FLAGS_step_telemetry_every": 4})
    obs.reset_step_telemetry()
    _train_loop(8)
    c = obs.step_counters()
    assert c["steps_seen"] == 8
    assert c["sampled"] == 2                      # steps 0 and 4
    # the sampled wall averages over the whole unsampled window
    assert step_telemetry.records()[-1]["window"] == 4


def test_step_telemetry_bitwise_and_no_retrace():
    """Telemetry is pure host-side observation: the loss trajectory is
    BITWISE identical with it on or off, and the executable is built
    exactly once either way."""
    paddle.set_flags({"FLAGS_step_telemetry": False})
    _, base = _train_loop(4)
    paddle.set_flags({"FLAGS_step_telemetry": True,
                      "FLAGS_step_telemetry_every": 1})
    obs.reset_step_telemetry()
    step, teled = _train_loop(4)
    assert teled == base
    assert obs.step_counters()["sampled"] == 4
    # the sampler never touches the compiled fn: one jitted object, and
    # more telemetered steps dispatch it without rebuilding
    jitted = step._jitted
    ids = jax.random.randint(jax.random.key(0), (2, 16), 0,
                             CFG.vocab_size, jnp.int32)
    step(ids)
    assert step._jitted is jitted


def test_step_telemetry_drift_sentinel(caplog):
    paddle.set_flags({"FLAGS_step_telemetry": True,
                      "FLAGS_step_time_drift_pct": 25.0})
    obs.reset_step_telemetry()
    import logging
    with caplog.at_level(logging.WARNING, "paddle_tpu.observability"):
        for i in range(6):
            step_telemetry.observe("t", i, wall_s=0.010)
        step_telemetry.observe("t", 6, wall_s=0.011)   # +10%: under gate
        assert obs.step_counters()["drift_alerts"] == 0
        step_telemetry.observe("t", 7, wall_s=0.020)   # +~90%: drift
    c = obs.step_counters()
    assert c["drift_alerts"] == 1
    assert any("step-time regression" in r.message for r in caplog.records)
    # the EWMA keeps tracking (slowly) after the alert
    assert c["wall_ema_s"] > 0.010


def test_drift_baseline_is_per_sampler():
    """Two models in one process (a sweep): each StepSampler owns its own
    EWMA baseline, so a slow second model never trips the fast first
    model's sentinel (and vice versa)."""
    paddle.set_flags({"FLAGS_step_telemetry": True,
                      "FLAGS_step_time_drift_pct": 25.0})
    obs.reset_step_telemetry()
    fast = step_telemetry.StepSampler("fast-model")
    slow = step_telemetry.StepSampler("slow-model")
    for i in range(5):
        step_telemetry.observe("fast", i, wall_s=0.001,
                               sentinel=fast._sentinel)
    # 10x slower model: would be a huge "drift" against fast's baseline,
    # but its own sentinel is still in warmup / tracking its own EWMA
    for i in range(5):
        step_telemetry.observe("slow", i, wall_s=0.010,
                               sentinel=slow._sentinel)
    assert obs.step_counters()["drift_alerts"] == 0
    assert fast._sentinel.ema == pytest.approx(0.001)
    assert slow._sentinel.ema == pytest.approx(0.010)


def test_step_telemetry_off_means_off():
    paddle.set_flags({"FLAGS_step_telemetry": False})
    obs.reset_step_telemetry()
    _train_loop(3)
    c = obs.step_counters()
    assert c["sampled"] == 0 and c["steps_seen"] == 0


# ---------------------------------------------------------------------------
# serving metrics ledger under concurrency (satellite)


def test_serving_metrics_concurrent_readers_writers():
    """Writer threads bump the ledger while reader threads snapshot it
    (the ServingSupervisor router/heartbeat pattern): no torn reads, no
    lost increments, derived values always computable."""
    from paddle_tpu.serving import metrics
    state = metrics.export_state()
    metrics.reset_serving_counters()
    N, W = 500, 4
    errors = []
    stop = threading.Event()

    def writer():
        for _ in range(N):
            metrics.bump("submitted")
            metrics.bump("tokens_out", 2)
            metrics.observe_ttft(0.001)
            metrics.observe_boundary(1, 2, 4)

    def reader():
        while not stop.is_set():
            try:
                c = metrics.serving_counters()
                # the snapshot is one consistent point in time: with a
                # single writer bumping submitted then tokens_out(+2),
                # every legal instant satisfies this envelope — a torn
                # (unlocked dict-copy mid-update) read would not
                s, t = c["submitted"], c["tokens_out"]
                assert 2 * s - 2 <= t <= 2 * s or s == 0, \
                    f"torn read: submitted={s} tokens_out={t}"
                metrics.serving_summary()
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    # tokens_out is bumped right after submitted by the same writer; with
    # multiple writers the invariant tokens==2*submitted only holds at
    # quiescence, so assert the torn-read-free invariant with ONE writer
    # first, then hammer with W writers for the no-lost-increment gate.
    readers = [threading.Thread(target=reader) for _ in range(2)]
    for t in readers:
        t.start()
    w = threading.Thread(target=writer)
    w.start()
    w.join()
    stop.set()
    for t in readers:
        t.join()
    assert not errors, errors[:1]
    assert metrics.serving_counters()["submitted"] == N

    ws = [threading.Thread(target=writer) for _ in range(W)]
    for t in ws:
        t.start()
    for t in ws:
        t.join()
    c = metrics.serving_counters()
    assert c["submitted"] == N * (W + 1), "lost increments under contention"
    assert c["tokens_out"] == 2 * N * (W + 1)
    assert c["boundaries"] == N * (W + 1)
    metrics.import_state(state)


def test_supervisor_telemetry_family():
    """A ServingSupervisor registers live per-replica gauges; the family
    empties out (weakref) once the supervisor is gone."""
    from paddle_tpu.serving.supervisor import ServingSupervisor
    from paddle_tpu.models.gpt_hybrid import init_gpt_params
    from paddle_tpu import serving
    params = init_gpt_params(CFG, jax.random.key(0))
    sup = ServingSupervisor(
        lambda: serving.Engine(params=params, config=CFG, num_slots=2,
                               max_seq_len=48, page_size=8,
                               prefill_chunk=8),
        num_replicas=2)
    tel = obs.collect("supervisor")
    assert tel["replicas"] == 2 and tel["alive"] == 2
    assert tel["replica0"]["up"] == 1
    flat = obs.snapshot()
    assert flat["supervisor.replica1.queue_depth"] == 0
    del sup, tel
    import gc
    gc.collect()
    assert obs.collect("supervisor") == {}


# ---------------------------------------------------------------------------
# FLOP estimator single source (satellite)


def test_flops_single_source():
    import bench
    from paddle_tpu.observability import flops as f
    # bench delegates to the observability estimator — same numbers by
    # construction, not by coincidence
    assert bench.model_flops_per_token(CFG, 32) == \
        f.model_flops_per_token(CFG, 32)
    assert bench.peak_flops_bf16("TPU v5 lite") == \
        f.peak_flops_bf16("TPU v5 lite") == 197e12
    # and tools_mfu_sweep consumes observability.flops, not a local copy
    import inspect
    import tools_mfu_sweep
    src = inspect.getsource(tools_mfu_sweep)
    assert "observability.flops" in src
    assert "6 * n_params" not in src              # the duplicated formula
    fpt, n = f.model_flops_per_token(CFG, 32)
    assert fpt > 6 * n                            # attention term counted
    assert f.dense_flops_per_token(10) == 60
    assert f.mfu(None, 1.0, 1.0) is None
    assert f.mfu(5.0, 1.0, 10.0) == 0.5


# ---------------------------------------------------------------------------
# smoke-tool rungs (fast deterministic sub-rung in tier-1; wall-clock
# overhead gate slow-marked)


def test_obs_smoke_fast_rungs():
    import tools_obs_smoke as smoke
    smoke.train_rung(steps=3, verbose=False)
    smoke.prometheus_rung(verbose=False)


@pytest.mark.slow
def test_obs_smoke_overhead_gate():
    import tools_obs_smoke as smoke
    smoke.overhead_rung()
