"""Per-request span tracing in the serving stack (observability/tracing).

Gates (the PR acceptance criteria):
  * a request's exported trace shows queue → prefill(-chunk) → decode →
    deliver spans whose timestamps reconcile with its recorded
    TTFT/latency TO THE FLOAT (spans reuse the ledger's perf_counter
    values);
  * spans survive a kill-and-resume: the restored request's trace keeps
    the pre-kill spans (shifted by the same clock re-anchoring as the
    request timestamps), gains a "restore" hop, and still reconciles;
  * steady-state trace-counter gates stay green with tracing enabled —
    tracing adds NO executables;
  * self-healing hops (drain requeue, supervisor replay) are recorded;
  * counter lifecycle across recovery (satellite): restored-vs-fresh
    metric ledgers documented and gated — restore_metrics=True replaces
    the ledger with the snapshot's and never double-counts
    requeued/replayed.
"""
import json
import os
import tempfile

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
from paddle_tpu import observability as obs, profiler, serving
from paddle_tpu.observability import tracing
from paddle_tpu.incubate.checkpoint import CheckpointManager
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.gpt_hybrid import init_gpt_params
from paddle_tpu.serving.supervisor import ServingSupervisor
from paddle_tpu.utils import fault_injection as fi

CFG = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
                max_seq_len=128, dropout=0.0, use_flash=False,
                compute_dtype="float32", remat=False)
_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = init_gpt_params(CFG, jax.random.key(0))
    return _PARAMS


def _engine(**kw):
    kw.setdefault("trace", True)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("num_slots", 4)   # unique batch shape for this file
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 16)
    return serving.Engine(params=_params(), config=CFG, **kw)


def _spans(rec, name):
    return [s for s in rec["spans"] if s["name"] == name]


def _span(rec, name):
    out = _spans(rec, name)
    assert len(out) == 1, f"expected one {name} span, got {out}"
    return out[0]


@pytest.fixture(autouse=True)
def _clean_traces():
    tracing.clear()
    yield
    tracing.clear()


# ---------------------------------------------------------------------------
# reconciliation (the acceptance gate)


def test_solo_request_trace_reconciles_exactly():
    """One request on a one-slot engine: the span timeline IS the
    request's latency story — queue starts at submit_t, first_token lands
    at the TTFT stamp, deliver at finish_t, and span durations tile the
    window."""
    eng = _engine(num_slots=1)
    req = serving.Request(np.arange(1, 10), max_new_tokens=5)
    results = eng.run([req])
    res = results[req.request_id]
    recs = tracing.traces()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["request_id"] == req.request_id
    assert rec["finish_reason"] == serving.LENGTH

    q = _span(rec, "queue")
    pf = _span(rec, "prefill_chunk")
    ft = _span(rec, "first_token")
    d = _span(rec, "deliver")
    decs = _spans(rec, "decode_step")

    # exact reconciliation: spans reuse the ledger's floats
    assert q["t0"] == req.submit_t
    assert ft["t0"] == req.first_token_t
    assert d["t0"] == req.finish_t
    assert (ft["t0"] - q["t0"]) == res.ttft == rec["ttft"]
    assert (d["t0"] - q["t0"]) == res.latency == rec["latency"]

    # structure: the prompt's one chunk emits token #1, decode the other 4
    assert pf["chunk"] == 16 and pf["tokens"] == 9
    assert len(decs) == 4
    # TTFT decomposes into its trace: the first token lands inside the
    # prefill+queue window (the emission timestamp follows the dispatch)
    assert q["t1"] <= pf["t0"]
    assert pf["t0"] <= ft["t0"]
    # the prompt's last chunk and the first decode dispatch share a
    # boundary, and a decode span covers its whole boundary (the stream's
    # inter-token gap): the chunk lies inside the first decode span
    assert decs[0]["t0"] <= pf["t0"] and pf["t1"] <= decs[0]["t1"]
    # the timeline is ordered and inside [submit, finish]
    ts = [q] + decs + [d]
    for a, b in zip(ts, ts[1:]):
        assert a["t1"] <= b["t0"] + 1e-9
        assert req.submit_t <= a["t0"] and a["t1"] <= req.finish_t + 1e-9
    # summed durations reconcile with latency: they tile the window minus
    # host bookkeeping between steps
    total = sum(s["t1"] - s["t0"] for s in ts)
    assert total <= res.latency + 1e-9
    assert total >= 0.25 * res.latency


def test_paged_chunked_prefill_spans():
    """A 20-token prompt on the 8/16 chunk ladder prefills as one
    16-chunk plus one 8-rung tail of 4 valid tokens — the trace shows
    exactly that, plus one decode span per emitted token after the
    first."""
    eng = _engine(num_slots=2)
    req = serving.Request(np.arange(1, 21), max_new_tokens=3)
    eng.run([req])
    rec = tracing.traces()[-1]
    chunks = _spans(rec, "prefill_chunk")
    assert [(c["offset"], c["tokens"], c["chunk"]) for c in chunks] == \
        [(0, 16, 16), (16, 4, 8)]
    assert len(_spans(rec, "decode_step")) == 2      # tokens 2 and 3
    q, ft, d = (_span(rec, n) for n in ("queue", "first_token", "deliver"))
    assert q["t0"] == req.submit_t
    assert (ft["t0"] - q["t0"]) == rec["ttft"]
    assert (d["t0"] - q["t0"]) == rec["latency"]
    # chunks happen between admission and first token
    assert all(q["t1"] <= c["t0"] and c["t1"] <= ft["t0"] for c in chunks)


def test_prefix_hit_recorded_in_trace():
    eng = _engine(num_slots=3)
    prompt = np.arange(1, 18)                        # 17 tokens: 2 full pages
    a = serving.Request(prompt.copy(), max_new_tokens=2)
    eng.run([a])
    b = serving.Request(prompt.copy(), max_new_tokens=2)
    eng.run([b])
    rec = next(r for r in tracing.traces()
               if r["request_id"] == b.request_id)
    hit = _span(rec, "prefix_hit")
    assert hit["tokens"] > 0 and hit["pages"] >= 1


# ---------------------------------------------------------------------------
# no-executable / steady-state gates with tracing on


def test_tracing_adds_no_executables():
    """Warm the engine's shapes with tracing OFF, then serve MORE traffic
    with tracing ON: every trace counter stays frozen — tracing never
    touches a compiled executable or a traced operand."""
    profiler.reset_serving_counters()
    rng = np.random.default_rng(7)

    def burst(eng, n):
        # 8-token prompts ride exactly ONE chunk rung ([1, 8])
        eng.run([serving.Request(rng.integers(0, 97, 8), max_new_tokens=4)
                 for _ in range(n)])

    # page_size=4 is UNIQUE across the test suite: the fused-step builder
    # memoizes on it, so this gate owns a fresh executable set and the
    # absolute trace count is immune to which suites ran before
    kw = dict(page_size=4, prefill_chunk=8)
    cold = _engine(trace=False, **kw)
    burst(cold, 5)
    warm = profiler.serving_counters()
    assert warm["paged_traces"] == 2        # [4,1] decode + one [1,8] rung

    traced = _engine(trace=True, **kw)
    burst(traced, 6)
    c = profiler.serving_counters()
    assert c["paged_traces"] == warm["paged_traces"], \
        "tracing re-traced the fused step"
    assert c["copy_traces"] == warm["copy_traces"]
    assert len(tracing.traces()) == 6


def test_flag_routes_engine_default():
    paddle.set_flags({"FLAGS_serving_trace": True})
    try:
        eng = _engine(trace=None)
        assert eng.trace_enabled
    finally:
        paddle.set_flags({"FLAGS_serving_trace": False})
    eng = _engine(trace=None)
    assert not eng.trace_enabled
    req = serving.Request([1, 2, 3], max_new_tokens=1)
    eng.run([req])
    assert req.trace is None                         # off = no span objects
    assert tracing.traces() == []


# ---------------------------------------------------------------------------
# snapshot survival (acceptance: spans survive kill-and-resume)


def test_trace_survives_kill_and_resume(tmp_path):
    eng = _engine(num_slots=2)
    mgr = CheckpointManager(os.fspath(tmp_path), async_save=False,
                            site="serving_snapshot")
    eng.attach_checkpoint(mgr, every=0)
    reqs = [serving.Request(np.arange(1, 21), max_new_tokens=6),
            serving.Request(np.arange(3, 12), max_new_tokens=8)]
    for r in reqs:
        eng.submit(r)
    for _ in range(4):                   # past prefill, mid-decode
        eng.step()
    assert any(r.state == serving.RUNNING for r in reqs)
    pre_spans = {r.request_id: len(r.trace.spans) for r in reqs
                 if r.trace is not None}
    eng.save_snapshot()
    del eng                              # the kill

    restored = _engine(num_slots=2, trace=False)  # flag need not
    restored.load_state_dict(mgr.restore())                # be on to resume
    results = restored.run()
    for r in reqs:
        assert results[r.request_id].finish_reason == serving.LENGTH
    recs = {r["request_id"]: r for r in tracing.traces()}
    for r in reqs:
        rec = recs[r.request_id]
        restore = _span(rec, "restore")
        q = _span(rec, "queue")
        d = _span(rec, "deliver")
        # pre-kill spans survived (count at least what the live request
        # had accumulated before the snapshot), shifted consistently
        assert len(rec["spans"]) > pre_spans[r.request_id]
        assert sum(1 for s in rec["spans"] if s["t0"] < restore["t0"]) \
            >= pre_spans[r.request_id]
        # reconciliation still exact across the resume: the spans and the
        # request timestamps shifted by the SAME delta
        assert (d["t0"] - q["t0"]) == rec["latency"]
        assert rec["ttft"] is not None
        assert _span(rec, "first_token")["t0"] - q["t0"] == rec["ttft"]
        # post-restore decode spans exist (work continued after resume)
        assert any(s["name"] == "decode_step" and s["t0"] > restore["t0"]
                   for s in rec["spans"])


def test_drain_requeue_hop_recorded():
    eng = _engine(num_slots=2)
    reqs = [serving.Request(np.arange(1, 10), max_new_tokens=6)
            for _ in range(2)]
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    drained = eng.drain()
    assert drained
    for r in drained:
        assert any(s["name"] == "requeue" for s in r.trace.spans)


def test_supervisor_replay_hop_recorded(tmp_path):
    """Kill a replica with NO snapshot dir: the survivor replays the dead
    replica's requests — each replayed request's trace records the
    replay hop and still delivers."""
    profiler.reset_serving_counters()

    def factory():
        return _engine(num_slots=2)

    sup = ServingSupervisor(factory, num_replicas=2)
    rng = np.random.default_rng(5)
    reqs = [serving.Request(rng.integers(0, 97, 9), max_new_tokens=5)
            for _ in range(4)]
    with fi.inject(fi.FaultPlan(kill_at_decode_step=2,
                                kill_engine_tag="replica0")):
        results = sup.run(reqs)
        assert fi.stats()["serving_kills"] == 1
    assert len(results) == len(reqs)
    assert profiler.recovery_counters()["dropped"] == 0
    assert profiler.recovery_counters()["replayed"] >= 1
    replayed = [r for r in tracing.traces()
                if any(s["name"] == "replay" for s in r["spans"])]
    assert replayed, "no replayed request carried the replay hop"
    for rec in replayed:
        assert rec["requeue_count"] >= 1
        assert _spans(rec, "deliver")


# ---------------------------------------------------------------------------
# export


def test_perfetto_and_jsonl_export():
    jsonl = tempfile.mktemp(suffix=".jsonl")
    sink = obs.JsonlTraceSink(jsonl)
    try:
        eng = _engine(num_slots=2)
        reqs = [serving.Request(np.arange(1, 8), max_new_tokens=3)
                for _ in range(3)]
        eng.run(reqs)
        path = tempfile.mktemp(suffix=".json")
        eng.export_trace(path)
        data = json.load(open(path))
        evs = data["traceEvents"]
        assert evs
        x = [e for e in evs if e["ph"] == "X"]
        inst = [e for e in evs if e["ph"] == "i"]
        meta = [e for e in evs if e["ph"] == "M"]
        assert x and inst and meta
        assert all("dur" in e and e["dur"] > 0 for e in x)
        assert all("ts" in e for e in x + inst)
        tids = {e["tid"] for e in x}
        # one thread per request and the engine's ``boundaries`` thread
        assert tids == {r.request_id for r in reqs} | {tracing.BOUNDARY_TID}
        os.unlink(path)
        sink.close()
        lines = [json.loads(ln) for ln in open(jsonl)]
        assert len(lines) == 3
        assert all(ln["spans"] for ln in lines)
    finally:
        try:
            sink.close()
        except Exception:  # noqa: BLE001
            pass
        if os.path.exists(jsonl):
            os.unlink(jsonl)


def test_trace_ring_is_bounded():
    paddle.set_flags({"FLAGS_trace_buffer": 8})
    try:
        eng = _engine(num_slots=2)
        for i in range(12):
            eng.run([serving.Request([1, 2, 3], max_new_tokens=1)])
        assert len(tracing.traces()) == 8
    finally:
        paddle.set_flags({"FLAGS_trace_buffer": 4096})


# ---------------------------------------------------------------------------
# counter lifecycle across recovery (satellite)


def test_restore_metrics_semantics_documented_and_gated(tmp_path):
    """The restored-vs-fresh ledger contract:

    * restore_metrics=False (default): the process ledger is UNTOUCHED
      except for the snapshot_restores bump — counters bumped since the
      snapshot (e.g. the drain's `requeued`) remain visible;
    * restore_metrics=True: the ledger is REPLACED by the snapshot's, so
      a preempt-drain cycle (snapshot BEFORE drain) restores with
      requeued as of the snapshot — the resumed slots were never requeued
      from the restored engine's point of view, and nothing double-counts.
    """
    from paddle_tpu.serving import metrics
    saved = metrics.export_state()
    try:
        profiler.reset_serving_counters()
        eng = _engine(num_slots=2)
        mgr = CheckpointManager(os.fspath(tmp_path), async_save=False,
                                site="serving_snapshot")
        eng.attach_checkpoint(mgr, every=0)
        reqs = [serving.Request(np.arange(1, 10), max_new_tokens=6)
                for _ in range(2)]
        for r in reqs:
            eng.submit(r)
        for _ in range(3):
            eng.step()
        eng.save_snapshot()              # ledger at snapshot: requeued == 0
        n_running = sum(r.state == serving.RUNNING for r in reqs)
        assert n_running == 2
        eng.drain()                      # live ledger: requeued == 2
        assert profiler.recovery_counters()["requeued"] == 2

        # fresh-restore (default): live ledger kept, one restore bump
        e1 = _engine(num_slots=2, trace=False)
        e1.load_state_dict(mgr.restore())
        c = profiler.recovery_counters()
        assert c["requeued"] == 2            # drain history NOT erased
        assert c["snapshot_restores"] == 1

        # restore_metrics=True: ledger replaced by the snapshot's —
        # requeued back to its pre-drain value, never double-counted by
        # the resumed (slots-intact) run
        e2 = _engine(num_slots=2, trace=False)
        e2.load_state_dict(mgr.restore(), restore_metrics=True)
        c = profiler.recovery_counters()
        assert c["requeued"] == 0
        assert c["snapshot_restores"] == 1   # the bump lands post-import
        results = e2.run()
        assert len(results) == 2
        c = metrics.serving_counters()
        assert c["requeued"] == 0            # resume is not a requeue
        assert c["completed"] == 2           # each request counted once
        assert c["replayed"] == 0
    finally:
        metrics.import_state(saved)


def test_supervisor_respawn_counts_once(tmp_path):
    """After a snapshot respawn, the recovery ledger tells one coherent
    story: one respawn, zero drops, and `replayed` counts only what the
    snapshot predated (never the resumed slots too)."""
    from paddle_tpu.serving import metrics
    saved = metrics.export_state()
    try:
        profiler.reset_serving_counters()

        def factory():
            return _engine(num_slots=2, trace=False)

        sup = ServingSupervisor(factory, num_replicas=2,
                                snapshot_dir=os.fspath(tmp_path),
                                snapshot_every=2)
        rng = np.random.default_rng(9)
        reqs = [serving.Request(rng.integers(0, 97, 9), max_new_tokens=5)
                for _ in range(4)]
        with fi.inject(fi.FaultPlan(kill_at_decode_step=3,
                                    kill_engine_tag="replica0")):
            results = sup.run(reqs)
            assert fi.stats()["serving_kills"] == 1
        assert len(results) == len(reqs)
        c = profiler.recovery_counters()
        assert c["dropped"] == 0
        assert c["respawns"] == 1
        assert c["snapshot_restores"] == 1
        # every request resolved exactly once at the supervisor level
        assert len({r for r in results}) == len(reqs)
        # replays are bounded by the dead replica's unacked work
        assert c["replayed"] <= len(reqs)
    finally:
        metrics.import_state(saved)
