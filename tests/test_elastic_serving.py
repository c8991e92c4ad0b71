"""Topology-elastic serving fleet — serving/elastic.py + the supervisor's
chip-loss reform on the 8-virtual-device CPU mesh.

The tentpole gates:

  * killing ONE chip of an mp group re-forms the group over its
    surviving chips at the largest viable mp degree, restoring its last
    snapshot through the PR 12 MP-PORTABLE path — every in-flight and
    queued request completes with ZERO drops and outputs BITWISE
    identical to an uninterrupted run (greedy AND sampled, any
    admission order);
  * grow-back returns the group to its original degree with zero drops
    and ZERO new traces (engine builders memoized per (cfg, mesh,
    rung));
  * the serving anomaly guard (FLAGS_serving_anomaly_policy) resolves a
    poisoned slot as finish_reason="error" with neighbors
    bitwise-stable and nothing published to the prefix cache; the
    default "off" trajectory is bitwise identical to the unguarded
    engine;
  * mid-reform submissions get a TYPED, retry_after-carrying
    EngineStoppedError (reforming=True) instead of a bare stop;
  * reforms land in the observability "elastic" family (group_reforms /
    grow_backs / degraded_groups / per-replica active_mp) and on traced
    requests as a "reform" hop.
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu.distributed import env as dist_env
from paddle_tpu.models.generation import generate_from_params
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.gpt_hybrid import init_gpt_params
from paddle_tpu.serving.elastic import viable_mp
from paddle_tpu.utils import fault_injection as fi

CFG = GPTConfig(vocab_size=96, hidden_size=64, num_layers=2, num_heads=4,
                max_seq_len=128, dropout=0.0, use_flash=False,
                compute_dtype="float32", remat=False)
_PARAMS = {}


def _params():
    if "p" not in _PARAMS:
        _PARAMS["p"] = init_gpt_params(CFG, jax.random.key(0))
    return _PARAMS["p"]


@pytest.fixture(autouse=True)
def _reset(devices8):
    yield
    paddle.set_flags({"FLAGS_comm_backend": "", "FLAGS_serving_mp": 0,
                      "FLAGS_serving_anomaly_policy": "off"})
    dist_env.set_mesh(None)
    fi.deactivate()


def _factory(**kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)

    def factory(i, mesh):
        return serving.Engine(params=_params(), config=CFG, mesh=mesh,
                              comm_backend="gspmd", **kw)

    return factory


def _ref_tokens(req):
    kw = ({"do_sample": True, "temperature": req.temperature,
           "top_p": req.top_p, "seed": req.seed} if req.do_sample else {})
    out = np.asarray(generate_from_params(
        _params(), np.asarray(req.prompt)[None], CFG,
        max_new_tokens=req.max_new_tokens, **kw)._data)
    return out[0, len(req.prompt):].tolist()


def _mixed_requests(n, seed):
    """Mixed greedy+sampled traffic with varied shapes."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        kw = ({"do_sample": True, "temperature": 0.7 + 0.1 * (i % 3),
               "top_p": 0.9, "seed": 11 + i} if i % 2 else {})
        reqs.append(serving.Request(rng.integers(0, 96, 4 + 3 * (i % 4)),
                                    max_new_tokens=4 + (i % 3), **kw))
    return reqs


def _step_until_mp(sup, replica, degree, limit=64):
    """Drive boundaries until a replica reaches the degree — BOUNDED, so
    a grow-back regression fails with a message instead of hanging CI."""
    for _ in range(limit):
        if sup.telemetry()[replica]["mp"] == degree:
            return
        sup.step()
    raise AssertionError(
        f"{replica} never reached mp={degree} within {limit} boundaries")


def _check_bitwise(results, reqs):
    for r in reqs:
        assert r.request_id in results, f"request {r.request_id} dropped"
        assert results[r.request_id].tokens == _ref_tokens(r), \
            f"request {r.request_id} diverged from uninterrupted run"


# ---------------------------------------------------------------------------
# the chaos gate: chip kill -> reform -> degraded -> grow-back


def test_chip_kill_reforms_mp4_group_bitwise(devices8, tmp_path):
    """One mp=4 group loses one chip mid-traffic: the supervisor re-forms
    it at mp=2 over the survivors through the mp-portable snapshot path;
    every request (mixed greedy+sampled) completes bitwise, zero drops."""
    reqs = _mixed_requests(5, seed=0)
    with fi.inject(fi.FaultPlan(serving_chip_loss_at={3: (1,)})):
        sup = serving.ServingSupervisor(
            _factory(), num_replicas=1, mp=4, devices=devices8[:4],
            snapshot_dir=os.fspath(tmp_path), snapshot_every=2)
        results = sup.run(reqs)
        assert fi.stats()["serving_chip_losses"] == 1
    _check_bitwise(results, reqs)
    assert profiler.serving_counters()["dropped"] == 0
    t = sup.telemetry()
    assert t["replica0"]["mp"] == 2           # degraded but serving
    assert t["degraded_groups"] == 1
    assert 1 not in t["replica0"]["group"]    # the dead chip left the mesh
    c = profiler.elastic_counters()
    assert c["group_reforms"] >= 1 and c["degraded_groups"] == 1
    assert c["active_mp_replica0"] == 2
    sup.shutdown()


def test_acceptance_two_mp2_groups_kill_and_growback(devices8, tmp_path):
    """THE acceptance gate: 2 mp=2 groups on 4 devices. Killing one chip
    re-forms the fleet and completes every in-flight and queued request
    with zero drops and outputs bitwise identical to an uninterrupted
    run (greedy AND sampled, shuffled admission order); grow-back
    returns to the original topology with zero drops and zero
    retraces."""
    reqs = _mixed_requests(8, seed=1)
    order = list(range(len(reqs)))
    np.random.default_rng(2).shuffle(order)   # any admission order
    with fi.inject(fi.FaultPlan(serving_chip_loss_at={3: (1,)})):
        sup = serving.ServingSupervisor(
            _factory(), num_replicas=2, mp=2, devices=devices8[:4],
            snapshot_dir=os.fspath(tmp_path), snapshot_every=2)
        for i in order:
            sup.submit(reqs[i])
        results = sup.run()
        # degraded while the chip is gone (the loss is sticky: no return
        # is scheduled, so the whole first wave serves on 3 chips)
        assert sup.telemetry()["replica0"]["mp"] == 1
        assert sup.telemetry()["degraded_groups"] == 1
    # plan deactivated = the chip came back (the in-plan
    # serving_chip_return_at path is covered by the whole-group test and
    # the smoke ladder): grow-back to the original topology — the
    # original mp=2 executables are memoized, so NO new trace appears
    traces = profiler.serving_counters()["paged_traces"]
    wave2 = _mixed_requests(4, seed=3)
    _step_until_mp(sup, "replica0", 2)
    for r in wave2:
        sup.submit(r)
    results2 = sup.run()
    assert profiler.serving_counters()["paged_traces"] == traces, \
        "grow-back must reuse the memoized original-degree executables"
    _check_bitwise(results, reqs)
    _check_bitwise(results2, wave2)
    assert profiler.serving_counters()["dropped"] == 0
    t = sup.telemetry()
    assert t["replica0"]["mp"] == 2 and t["replica1"]["mp"] == 2
    assert t["degraded_groups"] == 0
    assert sorted(t["replica0"]["group"]) == [0, 1]
    c = profiler.elastic_counters()
    assert c["grow_backs"] >= 1 and c["degraded_groups"] == 0
    sup.shutdown()


def test_whole_group_loss_replays_on_survivors(devices8, tmp_path):
    """Both chips of group 0 die: the group is down (zero viable mp) and
    its work replays on group 1 — zero drops, bitwise. When the chips
    return, the group comes back at full degree."""
    reqs = _mixed_requests(6, seed=4)
    with fi.inject(fi.FaultPlan(serving_chip_loss_at={2: (0, 1)},
                                serving_chip_return_at={8: (0, 1)})):
        sup = serving.ServingSupervisor(
            _factory(), num_replicas=2, mp=2, devices=devices8[:4],
            snapshot_dir=os.fspath(tmp_path), snapshot_every=2)
        results = sup.run(reqs)
        t = sup.telemetry()
        assert t["replica0"]["state"] == "down" or t["replica0"]["mp"] == 2
        _step_until_mp(sup, "replica0", 2)
    _check_bitwise(results, reqs)
    assert profiler.serving_counters()["dropped"] == 0
    assert sup.telemetry()["replica0"]["state"] == "up"
    sup.shutdown()


def test_elastic_grow_off_keeps_dead_group_down(devices8, tmp_path):
    """FLAGS_serving_elastic_grow=False: chip losses are STICKY. A group
    whose every chip died stays down even after its chips return (only
    the retry of a reform that failed mid-shrink may resurrect), its
    work serves on the survivor, and grow_backs never moves."""
    before = profiler.elastic_counters().get("grow_backs", 0)
    reqs = _mixed_requests(4, seed=9)
    with fi.inject(fi.FaultPlan(serving_chip_loss_at={2: (0, 1)},
                                serving_chip_return_at={5: (0, 1)})):
        sup = serving.ServingSupervisor(
            _factory(), num_replicas=2, mp=2, devices=devices8[:4],
            snapshot_dir=os.fspath(tmp_path), snapshot_every=2,
            elastic_grow=False)
        results = sup.run(reqs)
        for _ in range(4):              # boundaries well past the return
            sup.step()
        t = sup.telemetry()
        assert t["replica0"]["state"] == "down"
        assert t["replica0"]["mp"] == 0
    _check_bitwise(results, reqs)
    assert profiler.serving_counters()["dropped"] == 0
    assert profiler.elastic_counters().get("grow_backs", 0) == before
    sup.shutdown()


def test_draining_replica_not_degraded():
    """A rolling-restart drain is not chip degradation: a draining
    replica (chips healthy, out of rotation on purpose) must not trip
    the degraded_groups gauge operators alert on."""
    from paddle_tpu.serving.elastic import degraded_count

    class R:
        def __init__(self, idx, state, mp):
            self.idx, self.state, self.mp = idx, state, mp

    reps = [R(0, "draining", 2), R(1, "up", 2), R(2, "retired", 0),
            R(3, "down", 0), R(4, "up", 1)]
    assert degraded_count(reps, 2) == 2    # the down one + the shrunk one


def test_cancel_mid_grow_not_resurrected(devices8, tmp_path):
    """A request cancelled while its replica is mid-grow (engine nulled
    from the router's view, handle resolved directly) must not be
    resurrected from the live snapshot and decoded to completion on the
    grown engine — the grow path shares the loss path's acked/re-owned
    reconciliation."""
    with fi.inject(fi.FaultPlan(serving_chip_loss_at={3: (1,)})):
        sup = serving.ServingSupervisor(
            _factory(), num_replicas=1, mp=2, devices=devices8[:2],
            snapshot_dir=os.fspath(tmp_path), snapshot_every=2)
        long_req = serving.Request(np.arange(1, 6), max_new_tokens=64)
        sup.submit(long_req)
        for _ in range(4):
            sup.step()
        assert sup.telemetry()["replica0"]["mp"] == 1
    # chip back: hook the spawn so the cancel lands MID-grow, while the
    # old engine is already stopped for the handoff
    orig = sup._spawn_engine

    def spawn_after_cancel(rep):
        sup.cancel(long_req)
        return orig(rep)

    sup._spawn_engine = spawn_after_cancel
    _step_until_mp(sup, "replica0", 2)
    sup._spawn_engine = orig
    eng = sup._replicas[0].engine
    assert long_req.request_id not in {
        r.request_id for r in eng.live_requests()}, \
        "cancelled request resurrected onto the grown engine"
    res = sup.run()
    assert res[long_req.request_id].finish_reason == serving.CANCELLED
    sup.shutdown()


def test_failing_reform_backs_off(devices8, tmp_path):
    """A reform whose engine spawn keeps failing is retried with a
    DOUBLING boundary backoff — never a full spawn attempt at every
    boundary (which would stall the healthy groups) — and the work
    still serves on the survivors with zero drops."""
    calls = []
    with fi.inject(fi.FaultPlan(serving_chip_loss_at={1: (1,)})):
        sup = serving.ServingSupervisor(
            _factory(), num_replicas=2, mp=2, devices=devices8[:4],
            snapshot_dir=os.fspath(tmp_path), snapshot_every=2)
        orig = sup._spawn_engine

        def failing(rep):
            if rep.idx == 0:
                calls.append(sup._topo_step)
                raise RuntimeError("survivor cannot host the engine")
            return orig(rep)

        sup._spawn_engine = failing
        reqs = _mixed_requests(3, seed=12)
        results = sup.run(reqs)        # replays on replica1, zero drops
        n = len(calls)
        for _ in range(8):
            sup.step()
        assert len(calls) - n <= 4, \
            f"no backoff: {len(calls) - n} spawn attempts in 8 boundaries"
        sup._spawn_engine = orig
        _step_until_mp(sup, "replica0", 1)   # spaced retry still lands
    _check_bitwise(results, reqs)
    assert profiler.serving_counters()["dropped"] == 0
    sup.shutdown()


def test_chip_kill_without_snapshots_still_zero_drops(devices8):
    """No snapshot_dir: a chip-loss reform has nothing to restore and
    replays everything the group owed — still zero drops, still
    bitwise."""
    reqs = _mixed_requests(4, seed=5)
    with fi.inject(fi.FaultPlan(serving_chip_loss_at={3: (3,)})):
        sup = serving.ServingSupervisor(
            _factory(), num_replicas=2, mp=2, devices=devices8[:4])
        results = sup.run(reqs)
    _check_bitwise(results, reqs)
    assert profiler.serving_counters()["dropped"] == 0
    assert sup.telemetry()["replica1"]["mp"] == 1
    sup.shutdown()


def test_stale_chip_heartbeat_reforms_group(devices8, tmp_path):
    """Per-device liveness: a single FROZEN chip (its heartbeat writes
    silently dropped, the file ages past timeout) marks its whole group
    down and triggers the same reform path as an injected loss."""
    import time
    reqs = _mixed_requests(4, seed=6)
    with fi.inject(fi.FaultPlan(stale_heartbeat_ranks=[1])):
        sup = serving.ServingSupervisor(
            _factory(), num_replicas=2, mp=2, devices=devices8[:4],
            snapshot_dir=os.fspath(tmp_path / "snap"), snapshot_every=2,
            heartbeat_dir=os.fspath(tmp_path / "hb"),
            heartbeat_timeout=0.05)
        for r in reqs:
            sup.submit(r)
        sup.step()
        time.sleep(0.1)                 # chip 1's heartbeat file rots
        results = sup.run()
        assert sup.telemetry()["replica0"]["mp"] == 1
        assert fi.stats()["heartbeats_dropped"] > 0
    _check_bitwise(results, reqs)
    assert profiler.serving_counters()["dropped"] == 0
    sup.shutdown()


def test_reform_trace_hop(devices8, tmp_path):
    """A traced request crossing a reform carries a "reform" hop on its
    timeline (alongside the requeue/replay/restore hops)."""
    reqs = [serving.Request(np.arange(1, 10), max_new_tokens=8)]
    with fi.inject(fi.FaultPlan(serving_chip_loss_at={3: (1,)})):
        sup = serving.ServingSupervisor(
            _factory(trace=True), num_replicas=1, mp=2,
            devices=devices8[:2], snapshot_dir=os.fspath(tmp_path),
            snapshot_every=2)
        results = sup.run(reqs)
    _check_bitwise(results, reqs)
    from paddle_tpu.observability import tracing as obs_tracing
    rec = next(r for r in obs_tracing.traces()
               if r["request_id"] == reqs[0].request_id)
    names = [s["name"] for s in rec["spans"]]
    assert "reform" in names
    hop = next(s for s in rec["spans"] if s["name"] == "reform")
    assert hop["mp"] == 1 and hop["group"] == [0]
    sup.shutdown()


def test_cancel_after_grow_back(devices8, tmp_path):
    """A grow-back handoff mints FRESH Request objects (state_dict →
    load_state_dict): cancel() must route to the handle the new engine
    actually hosts — a stale pre-grow handle would silently no-op
    (Requests compare by identity)."""
    with fi.inject(fi.FaultPlan(serving_chip_loss_at={2: (1,)})):
        sup = serving.ServingSupervisor(
            _factory(), num_replicas=1, mp=2, devices=devices8[:2],
            snapshot_dir=os.fspath(tmp_path), snapshot_every=2)
        long_req = serving.Request(np.arange(1, 6), max_new_tokens=64)
        sup.submit(long_req)
        for _ in range(4):
            sup.step()
        assert sup.telemetry()["replica0"]["mp"] == 1
    # plan gone = the chip is back: grow while the request is mid-decode
    _step_until_mp(sup, "replica0", 2)
    sup.cancel(long_req)
    res = sup.run()
    assert res[long_req.request_id].finish_reason == serving.CANCELLED
    sup.shutdown()


# ---------------------------------------------------------------------------
# degraded-capacity operation + typed mid-reform errors (satellite)


def test_stop_for_reform_typed_error():
    eng = serving.Engine(params=_params(), config=CFG, num_slots=2,
                         max_seq_len=96, page_size=8, prefill_chunk=8)
    eng.stop_for_reform(retry_after=0.5)
    with pytest.raises(serving.EngineStoppedError) as ei:
        eng.submit(serving.Request([1, 2, 3], max_new_tokens=2))
    assert ei.value.reforming is True
    assert ei.value.retry_after == 0.5
    assert "reform" in str(ei.value)
    # a plain drain stays a plain (non-reforming) stop
    eng2 = serving.Engine(params=_params(), config=CFG, num_slots=2,
                          max_seq_len=96, page_size=8, prefill_chunk=8)
    eng2.drain()
    with pytest.raises(serving.EngineStoppedError) as ei:
        eng2.submit(serving.Request([1, 2, 3], max_new_tokens=2))
    assert ei.value.reforming is False and ei.value.retry_after is None


def test_all_reforming_fleet_backs_off_typed(devices8):
    """submit() with EVERY replica mid-reform: bounded retries, then a
    typed EngineStoppedError with reforming=True and a retry_after hint
    — the router knows the fleet comes back, unlike a dead fleet's bare
    error."""
    sup = serving.ServingSupervisor(
        _factory(), num_replicas=1, mp=2, devices=devices8[:2])
    rep = sup._replicas[0]
    rep.engine.stop_for_reform(retry_after=0.01)
    rep.state = "reforming"
    with pytest.raises(serving.EngineStoppedError) as ei:
        sup.submit(serving.Request([1, 2, 3], max_new_tokens=2))
    assert ei.value.reforming is True
    assert ei.value.retry_after is not None and ei.value.retry_after > 0
    # a genuinely dead fleet still raises the plain error
    rep.state = "down"
    rep.engine = None
    with pytest.raises(serving.EngineStoppedError) as ei:
        sup.submit(serving.Request([1, 2, 3], max_new_tokens=2))
    assert ei.value.reforming is False


def test_autoscaler_reads_routable_capacity(devices8, tmp_path):
    """The autoscale policy sees live ROUTABLE capacity: with one group
    down the fleet's alive count shrinks, so queue pressure is measured
    against what can actually serve (no spurious per-replica dilution by
    dead groups)."""
    from paddle_tpu.serving.slo import Autoscaler
    seen = []

    class Probe(Autoscaler):
        def decide(self, alive, **kw):
            seen.append(alive)
            return None

    with fi.inject(fi.FaultPlan(serving_chip_loss_at={1: (0, 1)})):
        sup = serving.ServingSupervisor(
            _factory(), num_replicas=2, mp=2, devices=devices8[:4],
            autoscale=Probe())
        reqs = _mixed_requests(3, seed=7)
        results = sup.run(reqs)
    _check_bitwise(results, reqs)
    assert 1 in seen         # after group 0 died, only group 1 counted
    sup.shutdown()


# ---------------------------------------------------------------------------
# mp_replica_meshes validation (satellite)


def test_mp_replica_meshes_validates_up_front(devices8):
    with pytest.raises(ValueError, match="mp=0"):
        serving.mp_replica_meshes(2, 0)
    with pytest.raises(ValueError, match="num_replicas=0"):
        serving.mp_replica_meshes(0, 2)
    with pytest.raises(ValueError, match="need 16 devices, only 8"):
        serving.mp_replica_meshes(4, 4)
    with pytest.raises(ValueError, match=r"5 devices.*mp=2"):
        serving.mp_replica_meshes(None, 2, devices8[:5])
    # derive the count from an arbitrary (non-contiguous) survivor set
    survivors = [devices8[0], devices8[2], devices8[3], devices8[6]]
    meshes = serving.mp_replica_meshes(None, 2, survivors)
    assert len(meshes) == 2
    assert [d.id for d in meshes[0].devices.flat] == [0, 2]
    assert [d.id for d in meshes[1].devices.flat] == [3, 6]


def test_viable_mp():
    assert viable_mp(4, 4) == 4
    assert viable_mp(4, 3) == 2     # largest divisor of 4 hostable by 3
    assert viable_mp(4, 1) == 1
    assert viable_mp(4, 0) == 0
    assert viable_mp(6, 5) == 3
    assert viable_mp(1, 8) == 1


# ---------------------------------------------------------------------------
# serving anomaly guard


def _engine(anomaly=None, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return serving.Engine(params=_params(), config=CFG, anomaly=anomaly,
                          **kw)


def test_anomaly_policy_off_default_bitwise():
    """Default off: no guard output, trajectory bitwise identical to the
    unguarded (PR 12) engine — the same memoized executable serves."""
    eng = _engine()
    assert eng.anomaly_policy == "off" and not eng._anomaly
    req = serving.Request(np.arange(2, 11), max_new_tokens=6)
    assert eng.run([req])[req.request_id].tokens == _ref_tokens(req)


def test_anomaly_policy_validation():
    with pytest.raises(ValueError, match="quarantine"):
        _engine(anomaly="retry")
    paddle.set_flags({"FLAGS_serving_anomaly_policy": "quarantine"})
    try:
        assert _engine().anomaly_policy == "quarantine"
    finally:
        paddle.set_flags({"FLAGS_serving_anomaly_policy": "off"})


def test_anomaly_quarantine_poisons_one_slot_only():
    """A NaN-poisoned KV page resolves ITS slot finish_reason="error" at
    the boundary; neighbors complete bitwise (batch rows never interact)
    and the poisoned prompt is NOT published to the prefix cache."""
    eng = _engine(anomaly="quarantine")
    reqs = [serving.Request(np.arange(1 + i, 8 + i), max_new_tokens=8,
                            **({"do_sample": True, "seed": 5,
                                "temperature": 0.8} if i == 2 else {}))
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    for _ in range(4):
        eng.step()
    victim = next(r for r in reqs if r.slot is not None)
    page = int(eng.pool.table[victim.slot][0])
    eng._kc = eng._kc.at[:, page].set(jnp.nan)    # flaky-chip simulation
    while eng.step():
        pass
    res = eng.pop_results()
    assert res[victim.request_id].finish_reason == serving.ERROR
    for r in reqs:
        if r is not victim:
            assert res[r.request_id].tokens == _ref_tokens(r), \
                "a poisoned slot leaked into a neighbor's stream"
    assert profiler.serving_counters()["anomalies_quarantined"] == 1
    _, shared, _ = eng.pool.lookup(victim.prompt)
    assert not shared, "poisoned prompt pages must not enter the prefix cache"


def test_anomaly_quarantine_mid_prefill():
    """Poison detected at first-token time (the final prefill chunk):
    the request errors with ZERO emitted tokens — garbage is never
    streamed."""
    eng = _engine(anomaly="quarantine", num_slots=1)
    bad = {**_params()}
    bad = {**bad, "lnf_g": jnp.full_like(_params()["lnf_g"], jnp.nan)}
    eng.swap_params(bad)
    req = serving.Request(np.arange(1, 7), max_new_tokens=4)
    res = eng.run([req])[req.request_id]
    assert res.finish_reason == serving.ERROR
    assert res.tokens == []


def test_anomaly_quarantine_does_not_poison_snapshot(tmp_path):
    """A snapshot taken after a quarantine restores into a healthy
    engine: the poisoned slot is gone, survivors resume bitwise."""
    from paddle_tpu.incubate.checkpoint import CheckpointManager
    eng = _engine(anomaly="quarantine")
    reqs = [serving.Request(np.arange(1 + i, 9 + i), max_new_tokens=8)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    for _ in range(4):
        eng.step()
    victim, other = (reqs[0], reqs[1]) if reqs[0].slot is not None \
        else (reqs[1], reqs[0])
    page = int(eng.pool.table[victim.slot][0])
    eng._kc = eng._kc.at[:, page].set(jnp.nan)
    eng.step()                                   # quarantine fires here
    snap = eng.state_dict()
    eng2 = _engine(anomaly="quarantine")
    eng2.load_state_dict(snap)
    while eng2.step():
        pass
    res = dict(eng.pop_results())
    res.update(eng2.pop_results())
    assert res[victim.request_id].finish_reason == serving.ERROR
    assert res[other.request_id].tokens == _ref_tokens(other)


# ---------------------------------------------------------------------------
# observability + chaos tooling


def test_elastic_family_serving_counters(devices8, tmp_path):
    from paddle_tpu import observability
    from paddle_tpu.observability import prometheus
    profiler.reset_elastic_counters()
    reqs = _mixed_requests(3, seed=8)
    with fi.inject(fi.FaultPlan(serving_chip_loss_at={2: (1,)})):
        sup = serving.ServingSupervisor(
            _factory(), num_replicas=2, mp=2, devices=devices8[:4],
            snapshot_dir=os.fspath(tmp_path), snapshot_every=2)
        sup.run(reqs)
    c = observability.collect("elastic")
    assert c["group_reforms"] >= 1
    assert c["active_mp_replica0"] == 1 and c["active_mp_replica1"] == 2
    assert c["degraded_groups"] == 1 and c["serving_chips_lost"] == 1
    assert c["reform_latency_s_last"] > 0
    text = prometheus.render()
    assert "elastic_group_reforms" in text
    assert "elastic_active_mp_replica0" in text
    assert "serving: 1 group-reforms" in profiler.elastic_summary()
    sup.shutdown()


def _smoke():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tools_fault_smoke",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools_fault_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fault_smoke_serving_elastic_fast(devices8):
    """tools_fault_smoke's serving-elastic ladder, fast deterministic
    sub-rung (tier-1): chip-kill-reform-resume + degraded-shed-grow-back
    with zero drops and the grow-back retrace gate."""
    out = _smoke().run_serving_elastic_ladder(deterministic=True)
    assert out["ok"], out
    assert out["requests_dropped"] == 0


@pytest.mark.slow
def test_fault_smoke_serving_elastic_full(devices8):
    out = _smoke().run_serving_elastic_ladder(deterministic=False)
    assert out["ok"], out
    assert out["requests_dropped"] == 0
