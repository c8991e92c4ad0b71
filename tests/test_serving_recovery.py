"""Self-healing serving: exact-resume engine snapshots, preemption drain
with request requeue, and the elastic ServingSupervisor.

Gates:
  * kill-and-resume of an engine with in-flight requests yields bitwise
    identical per-request outputs vs an uninterrupted run — greedy AND
    sampled, with the prefix cache on and off, including requests caught
    mid-chunked-prefill and prefix-shared siblings — with the snapshot round-tripped
    through the hardened CheckpointManager (CRC manifest on disk);
  * post-restore steady state reuses the existing executables: the trace
    counters do not move across snapshot/restore;
  * SIGTERM-style preemption drains at a step boundary: snapshot flushed,
    in-flight requests requeued (original arrival/deadline kept) instead
    of dropped, submit() afterwards raises EngineStoppedError;
  * supervisor chaos: killing one of N replicas mid-decode (abrupt, via
    the fault plan) drops ZERO requests — everything completes or is
    exactly replayed — deterministically on CPU; same for stale-heartbeat
    failover and rolling restart;
  * allocator balance/leak gates hold after restore.
"""
import os
import shutil
import tempfile

import numpy as np
import pytest
import jax

from paddle_tpu import profiler, serving
from paddle_tpu.incubate.checkpoint import CheckpointManager, Preempted
from paddle_tpu.models.generation import generate_from_params
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
    kw.setdefault("num_slots", 3)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return serving.Engine(params=_params(), config=CFG, **kw)


def _ref_tokens(prompt, max_new, **kw):
    out = np.asarray(generate_from_params(_params(), np.asarray(prompt)[None],
                                          CFG, max_new_tokens=max_new,
                                          **kw)._data)
    return out[0, len(prompt):].tolist()


def _sampled_kw(i):
    return {"do_sample": True, "temperature": 0.7 + 0.1 * i,
            "top_p": 0.85, "seed": 11 + i}


@pytest.fixture()
def ckpt_dir():
    d = tempfile.mkdtemp(prefix="serving_recovery_")
    yield d
    shutil.rmtree(d, ignore_errors=True)


def _requests(scenario, sampled):
    """Request mix per scenario; returns (requests, steps_before_kill)."""
    rng = np.random.default_rng(42)
    base = rng.integers(0, CFG.vocab_size, 21)
    if scenario == "prefix-shared":
        # sibling shares 2 full pages; exact dup forces live sharing + CoW
        prompts = [base.copy(),
                   np.concatenate([base[:16], rng.integers(0, 97, 4)]),
                   base.copy()]
        steps = 7
    elif scenario == "chunk-mid-prefill":
        # 37-token prompt over chunk=8: the kill lands with chunk_off <
        # prompt_len, so the snapshot captures a HALF-PREFILLED slot
        prompts = [rng.integers(0, 97, 37), rng.integers(0, 97, 5)]
        steps = 2
    else:                                   # plain mid-decode
        prompts = [rng.integers(0, 97, 9), rng.integers(0, 97, 13)]
        steps = 5
    reqs = []
    for i, p in enumerate(prompts):
        kw = _sampled_kw(i) if sampled else {}
        reqs.append(serving.Request(p, max_new_tokens=6 + i, **kw))
    return reqs, steps


def _golden(reqs):
    out = {}
    for r in reqs:
        kw = {}
        if r.do_sample:
            kw = {"do_sample": True, "temperature": r.temperature,
                  "top_p": r.top_p, "seed": r.seed}
        out[r.request_id] = _ref_tokens(r.prompt, r.max_new_tokens, **kw)
    return out


# ---------------------------------------------------------------------------
# kill / resume bitwise gates


@pytest.mark.parametrize("prefix_cache,sampled,scenario", [
    (False, False, "plain"),
    (False, True, "plain"),
    (True, False, "plain"),
    (True, True, "plain"),
    (True, False, "prefix-shared"),
    (True, True, "prefix-shared"),
    (True, False, "chunk-mid-prefill"),
    (True, True, "chunk-mid-prefill"),
])
def test_kill_resume_bitwise(ckpt_dir, prefix_cache, sampled, scenario):
    """Mid-flight kill + cold restart from a disk snapshot resumes every
    request token-for-token identically to an uninterrupted run."""
    reqs, steps = _requests(scenario, sampled)
    golden = _golden(reqs)

    eng = _engine(prefix_cache=prefix_cache)
    mgr = CheckpointManager(ckpt_dir, async_save=False,
                            site="serving_snapshot")
    eng.attach_checkpoint(mgr, every=0)
    for r in reqs:
        eng.submit(r)
    for _ in range(steps):
        eng.step()
    if scenario == "chunk-mid-prefill":
        assert any(
            eng._slots[b] is not None
            and eng._chunk_off[b] < eng._slots[b].prompt_len
            for b in range(eng.num_slots)), "kill did not land mid-prefill"
    eng.save_snapshot()
    pre = eng.pop_results()             # results delivered before the kill
    del eng                             # the "kill": engine object gone

    restored = _engine(prefix_cache=prefix_cache)
    snap = mgr.restore()                # CRC-verified read from disk
    restored.load_state_dict(snap)
    results = restored.run()
    results.update(pre)
    for r in reqs:
        assert results[r.request_id].tokens == golden[r.request_id], \
            f"{scenario} request {r.request_id} diverged after resume"
    bal = restored.pool.balance()
    assert bal["conserved"] and bal["refcounts_accounted"], bal


def test_kill_resume_bitwise_speculative(ckpt_dir):
    """Kill-and-resume MID-SPECULATIVE-TRAFFIC stays bitwise: drafts are
    boundary-atomic (no pending draft state exists between boundaries, so
    there is nothing to drain), the snapshot carries the draft config +
    params version under state["spec"], and the restored spec engine
    resumes every stream — greedy AND sampled, prefix-shared siblings
    included — token for token, with the paged allocator balanced."""
    reqs, _ = _requests("prefix-shared", sampled=True)
    golden = _golden(reqs)

    eng = _engine(speculate_k=4)
    mgr = CheckpointManager(ckpt_dir, async_save=False,
                            site="serving_snapshot")
    eng.attach_checkpoint(mgr, every=0)
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    assert eng.active_slots, "kill must land mid-traffic"
    state = eng.state_dict()
    assert state["spec"] == {"speculate_k": 4, "draft_source": "quant",
                             "draft_layers": 0,
                             "draft_params_version": eng.params_version}
    eng.save_snapshot()
    pre = eng.pop_results()
    del eng

    restored = _engine(speculate_k=4)
    restored.load_state_dict(mgr.restore())
    results = restored.run()
    results.update(pre)
    for r in reqs:
        assert results[r.request_id].tokens == golden[r.request_id], \
            f"spec request {r.request_id} diverged after resume"
    bal = restored.pool.balance()
    assert bal["conserved"] and bal["refcounts_accounted"], bal


def test_restore_does_not_retrace():
    """A restored engine re-dispatches the warm executables: the paged
    fused-step trace counter is IDENTICAL before the snapshot and after
    the resumed run."""
    profiler.reset_serving_counters()
    # num_slots=6 is UNIQUE across the suite: executables are shared per
    # shape process-wide, so borrowing another file's batch shape (e.g.
    # test_paged_serving's num_slots=5 warmup gate) would make this — or
    # that — test's warmup trace count order-dependent
    eng = _engine(num_slots=6)
    rng = np.random.default_rng(3)
    eng.run([serving.Request(rng.integers(0, 97, 11), max_new_tokens=4),
             serving.Request(rng.integers(0, 97, 19), max_new_tokens=5)])
    warm = profiler.serving_counters()

    reqs, steps = _requests("prefix-shared", sampled=True)
    for r in reqs:
        eng.submit(r)
    for _ in range(steps):
        eng.step()
    state = eng.state_dict()
    del eng
    restored = _engine(num_slots=6).load_state_dict(state)
    restored.run()
    c = profiler.serving_counters()
    assert c["paged_traces"] == warm["paged_traces"], \
        "snapshot restore re-traced the fused step"
    assert c["copy_traces"] <= max(warm["copy_traces"], 1)
    assert c["snapshot_restores"] >= 1


def test_snapshot_carries_results_and_metrics():
    """Unpopped results ride the snapshot; restore_metrics=True carries
    the SLO ledger across a cold restart."""
    profiler.reset_serving_counters()
    eng = _engine()
    r1 = serving.Request(np.arange(1, 8), max_new_tokens=3)
    r2 = serving.Request(np.arange(11, 30), max_new_tokens=12)
    eng.submit(r1)
    eng.submit(r2)
    while r1.state != serving.FINISHED:
        eng.step()
    state = eng.state_dict()            # r1 resolved but NOT popped
    tokens_then = profiler.serving_counters()["tokens_out"]
    assert tokens_then > 0
    del eng

    profiler.reset_serving_counters()   # simulate a cold process
    restored = _engine().load_state_dict(state, restore_metrics=True)
    assert profiler.serving_counters()["tokens_out"] == tokens_then
    results = restored.run()
    assert results[r1.request_id].tokens == _ref_tokens(np.arange(1, 8), 3)
    assert results[r2.request_id].tokens == _ref_tokens(np.arange(11, 30), 12)


def test_snapshot_meta_mismatch_rejected():
    eng = _engine()
    state = eng.state_dict()
    other = _engine(num_slots=2)
    with pytest.raises(ValueError, match="does not match"):
        other.load_state_dict(state)


# what the parent of the PR that removed the pooled layout wrote for a paged
# engine, key for key: snapshots load across that PR in both directions
PAGED_META_KEYS = {"kv_layout", "num_slots", "max_seq_len", "top_k",
                   "params_version", "cfg", "weight_dtype", "kv_dtype",
                   "adapters", "page_size", "prefill_chunk", "num_pages"}


def test_pooled_snapshot_refused():
    """The meta keeps its ``kv_layout`` key with the one value the program
    has; a snapshot that says ``"pooled"`` is input from outside it and is
    refused."""
    eng = _engine()
    state = eng.state_dict()
    assert set(state["meta"]) == PAGED_META_KEYS
    assert state["meta"]["kv_layout"] == "paged"
    state["meta"] = dict(state["meta"], kv_layout="pooled")
    with pytest.raises(ValueError, match="does not match"):
        _engine().load_state_dict(state)


# ---------------------------------------------------------------------------
# preemption drain


def _sigterm_after_one_step(eng):
    """Arrange a REAL SIGTERM right after the next fused step completes —
    lands between boundaries, exactly the defer-mode contract (the
    manager's flag is re-armed when run() installs the hook, so setting
    it by hand before run() would be erased)."""
    import signal
    orig, fired = eng.step, {"done": False}

    def step_then_sigterm():
        more = orig()
        if not fired["done"]:
            fired["done"] = True
            signal.raise_signal(signal.SIGTERM)
        return more

    eng.step = step_then_sigterm


def test_preemption_drain_requeues_and_cold_restart(ckpt_dir):
    """Deferred preemption at a step boundary: snapshot flushed with slots
    INTACT (cold restart resumes mid-decode bitwise), in-flight requests
    requeued with their original arrival, run() unwinds with Preempted."""
    eng = _engine()
    mgr = CheckpointManager(ckpt_dir, async_save=False,
                            site="serving_snapshot")
    eng.attach_checkpoint(mgr, every=0)
    a = serving.Request(np.arange(1, 20), max_new_tokens=12, deadline_s=60.0)
    eng.submit(a)
    for _ in range(4):
        eng.step()
    arrival = a.submit_t
    assert a.state == serving.RUNNING and a.tokens
    _sigterm_after_one_step(eng)        # a real preemption notice mid-run
    with pytest.raises(Preempted):
        eng.run()
    # drained + requeued, not dropped: original arrival and deadline kept
    assert a.state == serving.QUEUED and a.slot is None
    assert a.submit_t == arrival
    assert a.deadline == arrival + 60.0
    assert a.requeue_count == 1
    assert a.tokens == []               # replay re-emits deterministically
    assert eng.stopped

    restored = _engine()
    restored.load_state_dict(mgr.restore())
    res = restored.run()
    assert res[a.request_id].tokens == _ref_tokens(np.arange(1, 20), 12)
    c = profiler.serving_counters()
    assert c["preempt_drains"] >= 1


def test_submit_after_drain_raises_engine_stopped():
    eng = _engine()
    a = serving.Request(np.arange(1, 10), max_new_tokens=8)
    b = serving.Request(np.arange(20, 30), max_new_tokens=8)
    eng.submit(a)
    eng.submit(b)
    for _ in range(3):
        eng.step()
    drained = eng.drain()
    assert {r.request_id for r in drained} == {a.request_id, b.request_id}
    with pytest.raises(serving.EngineStoppedError) as ei:
        eng.submit(serving.Request([1, 2, 3], max_new_tokens=2))
    assert ei.value.queue_depth == 2
    assert set(ei.value.requeued) == {a.request_id, b.request_id}
    assert eng.step() is False          # dead state is never mutated
    # the drained requests serve to completion elsewhere, bitwise
    other = _engine()
    for r in drained:
        assert other.requeue(r)
    res = other.run()
    assert res[a.request_id].tokens == _ref_tokens(a.prompt, 8)
    assert res[b.request_id].tokens == _ref_tokens(b.prompt, 8)


def test_queue_full_error_carries_backoff_hints():
    eng = _engine(max_queue=2)
    eng.submit(serving.Request(np.arange(1, 5), max_new_tokens=2))
    eng.submit(serving.Request(np.arange(1, 6), max_new_tokens=2))
    with pytest.raises(serving.QueueFullError) as ei:
        eng.submit(serving.Request(np.arange(1, 7), max_new_tokens=2))
    assert ei.value.qsize == 2
    assert ei.value.max_queue == 2


def test_requeue_preserves_fcfs_and_cancel_race():
    """Requeue inserts at the ORIGINAL arrival position (FCFS survives a
    drain), and a cancel landing between drain and requeue is race-safe:
    the request resolves cancelled and the requeue skips it."""
    src = _engine()
    early = serving.Request(np.arange(1, 8), max_new_tokens=4)
    mid = serving.Request(np.arange(2, 9), max_new_tokens=4)
    src.submit(early)
    src.submit(mid)
    drained = src.drain()
    assert drained == [early, mid]      # arrival order

    dst = _engine()
    late = dst.submit(serving.Request(np.arange(3, 10), max_new_tokens=4))
    # cancel `mid` while it sits between drain and requeue
    src.cancel(mid)
    assert mid.state == serving.FINISHED
    assert dst.scheduler.requeue(mid) is False      # race-safe: skipped
    assert dst.requeue(early)
    # early arrived before late -> admitted first despite later requeue
    assert list(dst.scheduler._q) == [early, late]
    res = dst.run()
    assert res[early.request_id].tokens == _ref_tokens(early.prompt, 4)
    assert res[late.request_id].tokens == _ref_tokens(late.prompt, 4)
    assert src.pop_results()[mid.request_id].finish_reason == \
        serving.CANCELLED


# ---------------------------------------------------------------------------
# snapshot IO chaos through the hardened checkpoint path


def test_snapshot_io_error_retried_and_crc_fallback(ckpt_dir):
    """Injected OSError on the snapshot write retries through the shared
    hardened path; a corrupted newest snapshot quarantines and restore
    falls back to the previous good one — which still resumes bitwise."""
    from paddle_tpu.incubate.checkpoint import ckpt_counters
    eng = _engine()
    mgr = CheckpointManager(ckpt_dir, async_save=False, retries=2,
                            retry_backoff=0.01, site="serving_snapshot")
    eng.attach_checkpoint(mgr, every=0)
    a = serving.Request(np.arange(1, 20), max_new_tokens=10)
    eng.submit(a)
    before = ckpt_counters()
    with fi.inject(fi.FaultPlan(io_error_on_snapshots=[1])):
        for _ in range(3):
            eng.step()
        eng.save_snapshot()             # write #1 fails, retry succeeds
        for _ in range(2):
            eng.step()
        eng.save_snapshot()
    stats = fi.stats()
    assert stats["snapshot_io_errors"] == 1
    assert ckpt_counters()["save_retries"] - before["save_retries"] == 1
    # rot the newest snapshot: restore must fall back to the older one
    newest = mgr.latest_step()
    with open(os.path.join(ckpt_dir, f"step_{newest}", "state.pdckpt"),
              "r+b") as f:
        f.seek(-8, 2)
        f.write(b"\x00" * 8)
    restored = _engine()
    restored.load_state_dict(mgr.restore())
    assert mgr.last_restored_step < newest
    assert ckpt_counters()["quarantined"] - before["quarantined"] == 1
    res = restored.run()
    res.update(eng.pop_results())
    assert res[a.request_id].tokens == _ref_tokens(a.prompt, 10)


# ---------------------------------------------------------------------------
# supervisor chaos: zero requests dropped


def _supervisor_traffic(n=6, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        kw = _sampled_kw(i) if i % 2 else {}
        reqs.append(serving.Request(rng.integers(0, 97, 5 + 2 * i),
                                    max_new_tokens=5 + (i % 3), **kw))
    return reqs


def _factory():
    return serving.Engine(params=_params(), config=CFG, num_slots=3,
                          max_seq_len=96, page_size=8, prefill_chunk=8)


def test_supervisor_kill_one_replica_zero_dropped(ckpt_dir):
    """The acceptance rung: a fault plan kills one of 2 replicas
    mid-decode (abrupt — no flush); the supervisor respawns it from its
    last cadence snapshot and replays whatever the snapshot predates.
    Every request completes with bitwise-exact tokens; dropped == 0."""
    profiler.reset_serving_counters()
    sup = ServingSupervisor(_factory, num_replicas=2, snapshot_dir=ckpt_dir,
                            snapshot_every=2)
    reqs = _supervisor_traffic()
    golden = _golden(reqs)
    with fi.inject(fi.FaultPlan(kill_at_decode_step=3,
                                kill_engine_tag="replica0")):
        results = sup.run(reqs)
        assert fi.stats()["serving_kills"] == 1, \
            "the kill never fired — the rung proved nothing"
    assert len(results) == len(reqs)
    for r in reqs:
        assert results[r.request_id].tokens == golden[r.request_id], \
            f"request {r.request_id} not exactly recovered"
    c = profiler.serving_counters()
    assert c["dropped"] == 0
    assert c["respawns"] >= 1
    assert c["snapshots"] >= 1


def test_supervisor_replay_without_snapshots():
    """No snapshot_dir: recovery must come entirely from request replay on
    the surviving replica — still zero dropped, still bitwise."""
    profiler.reset_serving_counters()
    sup = ServingSupervisor(_factory, num_replicas=2, snapshot_dir=None)
    reqs = _supervisor_traffic(n=5, seed=1)
    golden = _golden(reqs)
    with fi.inject(fi.FaultPlan(kill_at_decode_step=2,
                                kill_engine_tag="replica1")):
        results = sup.run(reqs)
        assert fi.stats()["serving_kills"] == 1
    for r in reqs:
        assert results[r.request_id].tokens == golden[r.request_id]
    c = profiler.serving_counters()
    assert c["dropped"] == 0
    assert c["replayed"] >= 1


def test_supervisor_stale_heartbeat_failover(ckpt_dir):
    """A frozen replica (heartbeats suppressed, process never raises) is
    detected by the monitor and failed over; zero dropped."""
    profiler.reset_serving_counters()
    hb_dir = os.path.join(ckpt_dir, "hb")
    sup = ServingSupervisor(
        _factory, num_replicas=2,
        snapshot_dir=os.path.join(ckpt_dir, "snap"), snapshot_every=2,
        heartbeat_dir=hb_dir, heartbeat_timeout=0.05)
    reqs = _supervisor_traffic(n=4, seed=2)
    golden = _golden(reqs)
    import time
    with fi.inject(fi.FaultPlan(stale_heartbeat_ranks=[1])):
        for r in reqs:
            sup.submit(r)
        for _ in range(3):
            sup.step()
        time.sleep(0.1)                 # replica1's file goes stale
        results = sup.run()
    for r in reqs:
        assert results[r.request_id].tokens == golden[r.request_id]
    c = profiler.serving_counters()
    assert c["stale_failovers"] >= 1
    assert c["dropped"] == 0
    assert fi.stats()["heartbeats_dropped"] >= 1


def test_supervisor_rolling_restart_zero_dropped(ckpt_dir):
    """Drain-one-absorb-elsewhere rolling restart mid-traffic: every
    request completes bitwise, nothing dropped."""
    profiler.reset_serving_counters()
    sup = ServingSupervisor(_factory, num_replicas=2, snapshot_dir=ckpt_dir)
    reqs = _supervisor_traffic(n=6, seed=3)
    golden = _golden(reqs)
    for r in reqs:
        sup.submit(r)
    for _ in range(2):
        sup.step()
    sup.rolling_restart()
    results = sup.run()
    for r in reqs:
        assert results[r.request_id].tokens == golden[r.request_id]
    c = profiler.serving_counters()
    assert c["rolling_restarts"] == 1
    assert c["respawns"] >= 2           # every replica cycled
    assert c["dropped"] == 0
    assert sup.alive_replicas == 2


def test_supervisor_dead_fleet_resolves_dropped():
    """When the WHOLE fleet is gone (restart budget 0, no snapshots), an
    undeliverable request resolves terminally as DROPPED — run() converges
    to a visible failure instead of spinning — and cancel afterwards is a
    no-op."""
    profiler.reset_serving_counters()
    sup = ServingSupervisor(_factory, num_replicas=1, max_restarts=0)
    a = sup.submit(serving.Request(np.arange(1, 20), max_new_tokens=30))
    with fi.inject(fi.FaultPlan(kill_at_decode_step=2)):
        for _ in range(4):
            sup.step()
    assert sup.alive_replicas == 0
    res = sup.run()
    assert res[a.request_id].finish_reason == serving.DROPPED
    assert sup.pending() == 0
    assert profiler.serving_counters()["dropped"] == 1
    sup.cancel(a)                       # already delivered: no-op
    # run() drained its tracking state (long-running fleets must not grow)
    assert sup._requests == {} and sup._owner == {}


def test_supervisor_pop_results_dedups_after_stale_respawn(ckpt_dir):
    """pop_results forgets heavy state but keeps the delivered-id set: a
    replica respawned from a STALE snapshot recomputes old work without
    re-delivering it, and its moved/delivered requests are cancelled on
    the restored engine rather than resurrected."""
    sup = ServingSupervisor(_factory, num_replicas=2, snapshot_dir=ckpt_dir,
                            snapshot_every=1)
    reqs = _supervisor_traffic(n=4, seed=9)
    golden = _golden(reqs)
    first = sup.run(reqs)               # pops + records delivered ids
    assert sup._requests == {}
    for r in reqs:
        assert first[r.request_id].tokens == golden[r.request_id]
    # replica0's snapshot on disk still holds the old requests; kill it
    # with fresh traffic in flight: the respawn must serve only NEW work
    fresh = _supervisor_traffic(n=2, seed=10)
    with fi.inject(fi.FaultPlan(kill_at_decode_step=1,
                                kill_engine_tag="replica0")):
        second = sup.run(fresh)
    assert set(second) == {r.request_id for r in fresh}   # no re-delivery
    for r in fresh:
        assert second[r.request_id].tokens == _golden([r])[r.request_id]


def test_warm_restart_reuses_manager_without_insta_drain(ckpt_dir):
    """A preemption leaves mgr.preempted set; reattaching the SAME manager
    for a warm in-process restart must re-arm it (cleared on hook
    install), not preempt-drain the restored engine on its first step."""
    eng = _engine()
    mgr = CheckpointManager(ckpt_dir, async_save=False,
                            site="serving_snapshot")
    eng.attach_checkpoint(mgr, every=0)
    a = serving.Request(np.arange(1, 20), max_new_tokens=10)
    eng.submit(a)
    for _ in range(3):
        eng.step()
    _sigterm_after_one_step(eng)
    with pytest.raises(Preempted):
        eng.run()
    assert mgr.preempted                   # the handled preemption's residue
    warm = _engine().attach_checkpoint(mgr, every=0)
    warm.load_state_dict(mgr.restore())
    res = warm.run()                       # completes; no second Preempted
    assert res[a.request_id].tokens == _ref_tokens(a.prompt, 10)


def test_respawn_snapshot_ids_stay_monotonic(ckpt_dir):
    """A fresh engine reattached to a snapshot dir with history (supervisor
    respawn after a drain) must write snapshots that sort ABOVE the stale
    ones — otherwise _prune deletes them immediately and restore(None)
    keeps resurrecting pre-restart state."""
    mgr = CheckpointManager(ckpt_dir, keep_last_n=2, async_save=False,
                            site="serving_snapshot")
    eng = _engine().attach_checkpoint(mgr, every=2)
    eng.run([serving.Request(np.arange(1, 10), max_new_tokens=10)])
    stale = mgr.latest_step()
    assert stale is not None and stale >= 2

    fresh = _engine().attach_checkpoint(mgr, every=2)
    assert fresh._step_count >= stale
    fresh.run([serving.Request(np.arange(20, 30), max_new_tokens=10)])
    assert mgr.latest_step() > stale       # new snapshot survived _prune
    restored = _engine()
    restored.load_state_dict(mgr.restore())
    assert restored._step_count > stale    # restores the POST-restart state


def test_stale_restore_never_cancels_moved_request(ckpt_dir):
    """A replica restored from a snapshot that still contains a request
    since MOVED to another replica must cancel-and-purge its copy — the
    caller gets the real owner's bitwise stream, never a spurious
    CANCELLED result — and the hygiene cancel must not inflate the
    'cancelled' SLO counter (nobody cancelled anything)."""
    profiler.reset_serving_counters()
    sup = ServingSupervisor(_factory, num_replicas=2, snapshot_dir=ckpt_dir,
                            snapshot_every=1)
    r = serving.Request(np.arange(1, 20), max_new_tokens=12)
    sup.submit(r)
    for _ in range(4):
        sup.step()                         # mid-decode; snapshots on disk
    assert r.state == serving.RUNNING
    owner = sup._owner[r.request_id]
    rep, other = sup._replicas[owner], sup._replicas[1 - owner]
    # a rolling-restart-style move: drain the owner, requeue on the other
    for q in rep.engine.drain():
        other.engine.requeue(q)
        sup._owner[q.request_id] = other.idx
        sup._requests[q.request_id] = q
    rep.engine = sup._spawn_engine(rep)
    # the OLD owner dies and restores its STALE snapshot (which still
    # holds r mid-decode)
    sup._on_failure(rep, RuntimeError("boom"))
    results = sup.run()
    assert results[r.request_id].finish_reason == serving.LENGTH
    assert results[r.request_id].tokens == _ref_tokens(np.arange(1, 20), 12)
    assert profiler.serving_counters()["cancelled"] == 0


def test_finished_in_crashing_step_is_recomputed():
    """A request that RESOLVED on the dying replica in the very step that
    crashed (result lost, never collected) is recomputed exactly on the
    respawned fleet instead of being mistaken for a cancel and hanging
    pending() forever."""
    sup = ServingSupervisor(_factory, num_replicas=1)
    r = serving.Request(np.arange(1, 8), max_new_tokens=2)
    sup.submit(r)
    rep = sup._replicas[0]
    while r.state != serving.FINISHED:
        rep.engine.step()                  # resolve WITHOUT a collect
    sup._on_failure(rep, RuntimeError("died mid-step"))
    results = sup.run()
    assert results[r.request_id].tokens == _ref_tokens(np.arange(1, 8), 2)
    assert results[r.request_id].finish_reason == serving.LENGTH


def test_cross_host_restore_reanchors_deadlines():
    """perf_counter origins are per-boot-arbitrary in BOTH directions: a
    snapshot 'from another host' (snapshot_t skewed far behind AND far
    ahead of the local clock) must restore with deadlines still live —
    outage is measured by the wall-clock anchor, not perf skew."""
    for skew in (-864000.0, +864000.0):
        eng = _engine()
        a = serving.Request(np.arange(1, 20), max_new_tokens=10,
                            deadline_s=120.0)
        eng.submit(a)
        for _ in range(3):
            eng.step()
        state = eng.state_dict()
        # simulated foreign perf origin: EVERY value read from that clock
        # (snapshot anchor and request timestamps alike) shifts together
        state["snapshot_t"] += skew
        for spec in list(state["slots"]) + list(state["queue"]):
            if spec is None:
                continue
            for k in ("submit_t", "first_token_t", "finish_t"):
                if spec[k] is not None:
                    spec[k] += skew
        del eng
        restored = _engine().load_state_dict(state)
        res = restored.run()
        assert res[a.request_id].finish_reason == serving.LENGTH, skew
        assert res[a.request_id].tokens == _ref_tokens(a.prompt, 10), skew


def test_sigterm_during_final_step_still_flushes(ckpt_dir):
    """A preemption notice landing during the LAST fused step (step()
    returns False right after) must still flush + raise Preempted — not
    return normally and have the next hook install erase the notice."""
    eng = _engine()
    mgr = CheckpointManager(ckpt_dir, async_save=False,
                            site="serving_snapshot")
    eng.attach_checkpoint(mgr, every=0)
    a = serving.Request(np.arange(1, 8), max_new_tokens=4)
    eng.submit(a)
    for _ in range(2):   # boundary 1: chunk + fused decode (2 tok), then 1
        eng.step()
    assert len(a.tokens) == 3              # exactly one token left
    _sigterm_after_one_step(eng)           # lands as the work completes
    with pytest.raises(Preempted):
        eng.run()
    assert mgr.latest_step() is not None   # boundary snapshot flushed
    restored = _engine().load_state_dict(mgr.restore())
    res = restored.run()
    res.update(restored.pop_results())
    assert res[a.request_id].tokens == _ref_tokens(a.prompt, 4)


def test_supervisor_spill_does_not_inflate_ledger():
    """Routing past saturated replicas probes queue depth instead of
    trial-submitting: one logical request never bumps submitted/rejected
    once per full replica."""
    profiler.reset_serving_counters()
    sup = ServingSupervisor(
        lambda: serving.Engine(params=_params(), config=CFG, num_slots=3,
                               max_seq_len=96, page_size=8, prefill_chunk=8,
                               max_queue=1),
        num_replicas=2)
    sup.submit(serving.Request(np.arange(1, 5), max_new_tokens=2))
    sup.submit(serving.Request(np.arange(2, 6), max_new_tokens=2))
    with pytest.raises(serving.QueueFullError) as ei:
        sup.submit(serving.Request(np.arange(3, 7), max_new_tokens=2))
    # backoff hints are FLEET-WIDE totals (every queue the client competes
    # with), not whichever replica was probed last
    assert ei.value.qsize == 2
    assert ei.value.max_queue == 2
    c = profiler.serving_counters()
    assert c["submitted"] == 2             # the accepted ones only
    assert c["rejected"] == 0              # saturation probed, not trialed
    results = sup.run()
    assert len(results) == 2


def test_requeued_request_contributes_one_ttft_sample():
    """A drain/requeue round trip must not duplicate the request's TTFT
    sample (first_token_t is preserved by design; the histogram entry must
    be too)."""
    profiler.reset_serving_counters()
    from paddle_tpu.serving import metrics as smetrics
    eng = _engine()
    a = serving.Request(np.arange(1, 10), max_new_tokens=10)
    eng.submit(a)
    for _ in range(3):
        eng.step()
    assert a.tokens                        # first token emitted (1 sample)
    drained = eng.drain()
    dst = _engine()
    for q in drained:
        dst.requeue(q)
    dst.run()
    assert len(smetrics._ttft) == 1        # no duplicate from the replay


def test_rolling_restart_sustained_mixed_traffic(ckpt_dir):
    """rolling_restart under SUSTAINED mixed greedy+sampled traffic (new
    arrivals keep landing while each replica drains): zero drops, every
    stream bitwise — including requests admitted on the surviving
    neighbor while the other replica drained (neighbor stability) — and
    exactly ONE TTFT histogram sample per unique request despite the
    drain/requeue round trips (extends the PR 7/9 counter-lifecycle
    gates)."""
    profiler.reset_serving_counters()
    from paddle_tpu.serving import metrics as smetrics

    sup = ServingSupervisor(
        lambda: _engine(max_queue=64), num_replicas=2,
        snapshot_dir=ckpt_dir)
    rng = np.random.default_rng(23)
    reqs, i = [], 0

    def arrive(n):
        nonlocal i
        for _ in range(n):
            kw = _sampled_kw(i) if i % 2 else {}
            r = serving.Request(rng.integers(0, 97, 5 + (i % 4) * 2),
                                max_new_tokens=4 + i % 3, **kw)
            sup.submit(r)
            reqs.append(r)
            i += 1

    arrive(6)
    for _ in range(3):
        sup.step()
    arrive(4)                                 # traffic keeps flowing...
    sup.rolling_restart(absorb_steps=1)       # ...through the restart
    arrive(4)
    results = sup.run()
    gold = _golden(reqs)
    assert len(results) == len(reqs)
    for r in reqs:
        assert results[r.request_id].tokens == gold[r.request_id], \
            f"request {r.request_id} diverged across the rolling restart"
        assert results[r.request_id].finish_reason in ("stop", "length")
    c = profiler.serving_counters()
    assert c["dropped"] == 0
    assert c["rolling_restarts"] == 1
    assert c["requeued"] > 0                  # the restart DID disrupt work
    # one TTFT sample per unique request: requeues must not double-count
    assert len(smetrics._ttft) == len(reqs)


# ---------------------------------------------------------------------------
# tensor-parallel (mp-sharded) engine snapshots


@pytest.mark.parametrize("sampled", [False, True])
def test_mp_kill_resume_bitwise_through_checkpoint(ckpt_dir, sampled,
                                                   devices8):
    """Kill-and-resume of an mp=2 SHARDED engine: the state_dict round
    trips the head-sharded KV pool through the hardened CheckpointManager
    (device_get gathers the global pool; restore lays the head axis back
    out across chips), and every mid-decode request resumes bitwise —
    greedy and sampled."""
    reqs, steps = _requests("plain", sampled)
    golden = _golden(reqs)

    def _mp_engine():
        return serving.Engine(params=_params(), config=CFG, num_slots=3,
                              max_seq_len=96, page_size=8, prefill_chunk=8,
                              mp=2, comm_backend="gspmd")

    eng = _mp_engine()
    mgr = CheckpointManager(ckpt_dir, async_save=False,
                            site="serving_snapshot")
    eng.attach_checkpoint(mgr, every=0)
    for r in reqs:
        eng.submit(r)
    for _ in range(steps):
        eng.step()
    eng.save_snapshot()
    pre = eng.pop_results()
    del eng

    restored = _mp_engine()
    restored.load_state_dict(mgr.restore())
    assert restored._kc.sharding.is_equivalent_to(
        restored._kv_sharding, restored._kc.ndim), \
        "restored KV pool lost its head sharding"
    results = restored.run()
    results.update(pre)
    for r in reqs:
        assert results[r.request_id].tokens == golden[r.request_id], \
            f"mp request {r.request_id} diverged after sharded resume"
    bal = restored.pool.balance()
    assert bal["conserved"] and bal["refcounts_accounted"], bal


def test_mp_restore_does_not_retrace(devices8):
    """A restored mp engine re-dispatches the already-compiled sharded
    fused step — paged trace counters do not move across
    snapshot/restore (builders are memoized per (config, mesh, rung))."""
    def _mp_engine():
        return serving.Engine(params=_params(), config=CFG, num_slots=3,
                              max_seq_len=96, page_size=8, prefill_chunk=8,
                              mp=2, comm_backend="gspmd")

    eng = _mp_engine()
    reqs, steps = _requests("plain", False)
    for r in reqs:
        eng.submit(r)
    for _ in range(steps):
        eng.step()
    snap = eng.state_dict()
    before = profiler.serving_counters()["paged_traces"]
    restored = _mp_engine()
    restored.load_state_dict(snap)
    restored.run()
    assert profiler.serving_counters()["paged_traces"] == before, \
        "sharded restore re-traced the fused step"


def _load_smoke():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tools_fault_smoke",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools_fault_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fault_smoke_serving_subrung():
    """tools_fault_smoke's serving chaos ladder in deterministic tiny
    mode: kill-resume-decode bitwise, zero requests dropped — no
    wall-clock gates (the full ladder with latency reporting is slow)."""
    mod = _load_smoke()
    out = mod.run_serving_ladder(quick=True, deterministic=True)
    assert out["requests_dropped"] == 0
    assert out["kill_resume"]["bitwise"]
    assert out["rolling_restart"]["bitwise"]


@pytest.mark.slow
def test_fault_smoke_serving_full_ladder():
    mod = _load_smoke()
    out = mod.run_serving_ladder(quick=False)
    assert out["requests_dropped"] == 0
    assert out["kill_resume"]["bitwise"]
    assert out["rolling_restart"]["bitwise"]
    assert out["snapshot_io"]["recovered"]
    assert out["stale_heartbeat"]["bitwise"]
    assert out["recovery_p99_s"] < 60.0
