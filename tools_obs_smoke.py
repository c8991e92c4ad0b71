#!/usr/bin/env python
"""Observability smoke: run a short train loop and a serving burst with the
unified telemetry ON, then gate the artifacts:

  * the exported per-request Perfetto/Chrome-trace JSON loads and its
    span timeline reconciles with the recorded TTFT/latency;
  * the JSONL trace sink emits one parseable line per finished request;
  * the Prometheus /metrics page parses line-by-line and carries every
    counter family;
  * steady-state trace-counter gates stay green with telemetry on
    (paged_traces frozen after warmup — tracing adds no executables);
  * telemetry-on vs telemetry-off train step time differs by <3%
    (the zero-overhead contract; full rung only — wall-clock gates are
    slow-marked, tier-1 runs the deterministic structural rungs).

  python tools_obs_smoke.py          # full ladder (incl. overhead gate)
  python tools_obs_smoke.py --fast   # structural rungs only (tier-1)
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

OVERHEAD_GATE_PCT = 3.0


def _tiny_cfg():
    from paddle_tpu.models.gpt import GPTConfig
    return GPTConfig(vocab_size=97, hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=128, dropout=0.0,
                     use_flash=False, compute_dtype="float32", remat=False)


def _flags(**kw):
    import paddle_tpu as paddle
    paddle.set_flags(kw)


def train_rung(steps=8, verbose=True):
    """Short HybridTrainStep loop with step telemetry on: sampled records
    exist, carry the dispatch/sync split, and report MFU from the shared
    FLOP estimator."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.models.gpt_hybrid import HybridTrainStep

    _flags(FLAGS_step_telemetry=True, FLAGS_step_telemetry_every=1)
    obs.reset_step_telemetry()
    try:
        cfg = _tiny_cfg()
        opt = paddle.optimizer.AdamW(1e-3)
        step = HybridTrainStep(cfg, opt)
        ids = jax.random.randint(jax.random.key(0), (2, 32), 0,
                                 cfg.vocab_size, jnp.int32)
        for _ in range(steps):
            step(ids)
        c = obs.step_counters()
        assert c["sampled"] == steps, c
        assert c["last_dispatch_s"] is not None
        assert c["last_sync_s"] is not None
        # MFU exists only against a known TPU peak; off-TPU it is None
        on_tpu = jax.default_backend() == "tpu"
        assert (c["last_mfu"] is not None and c["last_mfu"] > 0) == on_tpu
        assert c["flops_per_step"] > 0
        if verbose:
            print(f"TRAIN rung: {obs.step_summary()}", flush=True)
        return c
    finally:
        _flags(FLAGS_step_telemetry=False, FLAGS_step_telemetry_every=8)


def serving_rung(verbose=True):
    """Serving burst with span tracing on: every finished request's trace
    reconciles (queue.t0==submit, first_token==TTFT stamp,
    deliver==finish), the Perfetto export loads, the JSONL sink parses,
    and the paged trace counters freeze after warmup."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import serving, observability as obs
    from paddle_tpu.observability import tracing
    from paddle_tpu.models.gpt_hybrid import init_gpt_params
    from paddle_tpu.serving import metrics

    _flags(FLAGS_serving_trace=True)
    tracing.clear()
    jsonl_path = tempfile.mktemp(suffix=".jsonl", prefix="obs_trace_")
    sink = obs.JsonlTraceSink(jsonl_path)
    try:
        cfg = _tiny_cfg()
        params = init_gpt_params(cfg, jax.random.key(0))
        eng = serving.Engine(params=params, config=cfg, num_slots=3,
                             max_seq_len=96, page_size=8,
                             prefill_chunk=16)
        rng = np.random.default_rng(0)
        reqs = [serving.Request(rng.integers(0, cfg.vocab_size, 12),
                                max_new_tokens=4) for _ in range(6)]
        results = eng.run(reqs)
        assert len(results) == len(reqs)
        base = metrics.serving_counters()["paged_traces"]
        # steady-state gate: more traffic over warm shapes must not trace
        more = [serving.Request(rng.integers(0, cfg.vocab_size, 12),
                                max_new_tokens=4) for _ in range(4)]
        eng.run(more)
        assert metrics.serving_counters()["paged_traces"] == base, \
            "tracing added executables"

        recs = tracing.traces()
        assert len(recs) >= len(reqs) + len(more)
        for rec in recs:
            spans = {s["name"]: s for s in rec["spans"]}
            q, ft, d = spans["queue"], spans["first_token"], spans["deliver"]
            assert abs((ft["t0"] - q["t0"]) - rec["ttft"]) < 1e-9
            assert abs((d["t0"] - q["t0"]) - rec["latency"]) < 1e-9

        trace_path = tempfile.mktemp(suffix=".json", prefix="obs_perfetto_")
        eng.export_trace(trace_path)
        data = json.load(open(trace_path))           # "Perfetto JSON loads"
        assert data["traceEvents"], "empty trace export"
        assert all("ph" in ev and "pid" in ev for ev in data["traceEvents"])
        os.unlink(trace_path)

        sink.close()
        lines = [json.loads(ln) for ln in open(jsonl_path)]
        assert len(lines) == len(recs)
        assert all("spans" in ln and "request_id" in ln for ln in lines)
        if verbose:
            print(f"SERVING rung: {len(recs)} traces, "
                  f"{sum(len(r['spans']) for r in recs)} spans, "
                  f"paged_traces frozen at {base}", flush=True)
        return recs
    finally:
        _flags(FLAGS_serving_trace=False)
        try:
            sink.close()
        except Exception:  # noqa: BLE001 — already closed on success
            pass
        if os.path.exists(jsonl_path):
            os.unlink(jsonl_path)


def prometheus_rung(verbose=True):
    """Start the /metrics endpoint on an ephemeral port, scrape it, parse
    the exposition page, and check every counter family is present."""
    from urllib.request import urlopen
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import prometheus

    srv = obs.start_metrics_server(port=0)
    try:
        text = urlopen(srv.url, timeout=10).read().decode()
        parsed = prometheus.parse(text)              # "the page parses"
        assert parsed, "empty exposition page"
        for fam in ("dispatch", "serving", "comm", "mp_comm", "fault",
                    "recovery", "step"):
            assert any(k.startswith(f"paddle_tpu_{fam}_") for k in parsed), \
                f"family {fam} missing from /metrics"
        if verbose:
            print(f"PROMETHEUS rung: {len(parsed)} series at {srv.url}",
                  flush=True)
        return parsed
    finally:
        obs.stop_metrics_server()


def overhead_rung(steps=40, trials=4, verbose=True):
    """Telemetry-on vs telemetry-off steady-state train step time, best of
    ``trials`` with the on/off measurements INTERLEAVED (machine-load
    drift between two back-to-back blocks would otherwise dwarf the <3%
    gate; wall-clock: full rung only)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.models.gpt_hybrid import HybridTrainStep

    cfg = _tiny_cfg()
    ids = jax.random.randint(jax.random.key(0), (2, 32), 0,
                             cfg.vocab_size, jnp.int32)

    def make_step():
        paddle.seed(0)
        step = HybridTrainStep(cfg, paddle.optimizer.AdamW(1e-3))
        for _ in range(5):                       # warm the executable
            step(ids)
        jax.block_until_ready(step.params["wte"])
        return step

    def one_trial(step, telemetry):
        _flags(FLAGS_step_telemetry=telemetry, FLAGS_step_telemetry_every=8)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(ids)
        jax.block_until_ready(loss)
        return (time.perf_counter() - t0) / steps

    try:
        obs.reset_step_telemetry()
        step = make_step()
        off = on = float("inf")
        for _ in range(trials):                  # interleave off/on pairs
            off = min(off, one_trial(step, False))
            on = min(on, one_trial(step, True))
        diff = (on - off) / off * 100.0
        if verbose:
            print(f"OVERHEAD rung: off {off * 1e3:.3f}ms  on "
                  f"{on * 1e3:.3f}ms  diff {diff:+.2f}% "
                  f"(gate <{OVERHEAD_GATE_PCT}%)", flush=True)
        assert diff < OVERHEAD_GATE_PCT, \
            f"telemetry overhead {diff:.2f}% exceeds {OVERHEAD_GATE_PCT}%"
        return off, on
    finally:
        _flags(FLAGS_step_telemetry=False, FLAGS_step_telemetry_every=8)


def main():
    fast = "--fast" in sys.argv
    train_rung()
    serving_rung()
    prometheus_rung()
    if not fast:
        overhead_rung()
    print("OBS SMOKE OK" + (" (fast)" if fast else ""), flush=True)


if __name__ == "__main__":
    main()
