"""SLO-driven multi-tenant serving (serving/slo.py + the class-aware
scheduler/engine/supervisor wiring).

Gates:
  * flags off = the strict-FCFS default path (the parity suites cover
    bitwise; here: no policy object is even constructed);
  * class-aware admission (interactive first) + WFQ tenant fairness,
    incl. weights;
  * preemptive admission: a deadline-at-risk interactive evicts the
    youngest best_effort slot, whose replay stays BITWISE (the PR 7
    requeue machinery);
  * load shedding: sustained overload sheds lowest-class queued work
    with retry-after hints from the live drain rate, refuses new
    best_effort while latched, recovers, and the ledger/summary show it;
  * unified deadline boundary (now >= deadline) + queue-wait recording
    for EXPIRED/SHED;
  * hot weight swap: same-shape, zero retraces, prefix cache
    invalidated, version stamped end to end (results, snapshots,
    telemetry), version-mismatched snapshots fall back to replay;
  * autoscaler policy (hysteresis + cooldown) and supervisor
    grow/shrink through the spawn/drain machinery;
  * per-tenant token-bucket rate limits (ShedError with exact hints);
  * the satellite fixes: draining replicas unroutable, fleet-wide
    QueueFullError totals;
  * the tools_slo_smoke.py chaos ladder (quick rungs in tier-1, the p99
    gate slow-marked).
"""
import os
import time

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.serving import metrics as smetrics
from paddle_tpu.serving import ShedError
from paddle_tpu.serving.slo import Autoscaler, DrainRate, TokenBucket
from paddle_tpu.models.generation import generate_from_params
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.gpt_hybrid import init_gpt_params
from paddle_tpu.utils import fault_injection as fi

CFG = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
                max_seq_len=128, dropout=0.0, use_flash=False,
                compute_dtype="float32", remat=False)
_PARAMS = {}


def _params(seed=0):
    if seed not in _PARAMS:
        _PARAMS[seed] = init_gpt_params(CFG, jax.random.key(seed))
    return _PARAMS[seed]


def _engine(**kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    params = kw.pop("params", None)
    return serving.Engine(params=params if params is not None else _params(),
                          config=CFG, **kw)


def _ref(prompt, max_new, params_seed=0, **kw):
    out = np.asarray(generate_from_params(
        _params(params_seed), np.asarray(prompt)[None], CFG,
        max_new_tokens=max_new, **kw)._data)
    return out[0, len(prompt):].tolist()


@pytest.fixture(autouse=True)
def _reset():
    smetrics.reset_serving_counters()
    yield
    paddle.set_flags({
        "FLAGS_serving_priority_classes": False,
        "FLAGS_serving_shed": False,
        "FLAGS_serving_shed_window": 4,
        "FLAGS_serving_preempt_margin_s": 0.0,
        "FLAGS_serving_tenant_rate": 0.0,
        "FLAGS_serving_autoscale": False,
        "FLAGS_serving_class_deadline_interactive": 0.0,
    })
    fi.deactivate()


# ---------------------------------------------------------------------------
# defaults / request surface


def test_flags_off_no_policy_objects():
    """Default engine: strict FCFS, no shed policy, no class deadlines —
    the pre-SLO path (whose bitwise parity the serving suites gate)."""
    eng = _engine()
    assert eng.priority_mode is False
    assert eng._shed is None
    assert eng.scheduler.priority is False
    assert eng.params_version == 0
    # priority/tenant are carried but inert: a best_effort request is
    # served strict-FCFS behind an earlier batch one
    a = serving.Request(np.arange(1, 6), max_new_tokens=2,
                        priority="best_effort")
    b = serving.Request(np.arange(2, 7), max_new_tokens=2,
                        priority="interactive")
    eng1 = _engine(num_slots=1)
    eng1.submit(a)
    eng1.submit(b)
    res = eng1.run()
    assert res[a.request_id].ttft < res[b.request_id].ttft  # FCFS held


def test_unknown_priority_class_rejected():
    with pytest.raises(ValueError, match="unknown priority class"):
        serving.Request(np.arange(1, 4), priority="platinum")


def test_request_state_roundtrip_carries_slo_fields():
    r = serving.Request(np.arange(1, 6), max_new_tokens=3,
                        priority="best_effort", tenant="acme")
    r.params_version = 5
    s = r.to_state()
    r2 = serving.Request.from_state(s)
    assert (r2.priority, r2.tenant, r2.params_version) == \
        ("best_effort", "acme", 5)
    c = r.replay_copy()
    assert (c.priority, c.tenant) == ("best_effort", "acme")
    # results carry them too
    r._finish(serving.LENGTH)
    res = r.result()
    assert (res.priority, res.tenant, res.params_version) == \
        ("best_effort", "acme", 5)


def test_deadline_boundary_unified():
    """ONE boundary predicate everywhere: expired from the first instant
    now >= deadline (the deadline itself is outside the window)."""
    r = serving.Request(np.arange(1, 4), deadline_s=5.0)
    r.submit_t = 100.0
    assert not r.expired(104.999)
    assert r.expired(105.0)          # the boundary instant counts
    assert r.expired(105.001)
    # scheduler.expire and admit use the same predicate
    sched = serving.Scheduler()
    sched.submit(r)
    assert sched.expire(now=104.9) == []
    expired = sched.expire(now=105.0)
    assert expired == [r] and r.finish_reason == serving.EXPIRED


# ---------------------------------------------------------------------------
# class-aware admission + WFQ


def _queued(prompt_start, cls="batch", tenant="default", t=None):
    r = serving.Request(np.arange(prompt_start, prompt_start + 4),
                        max_new_tokens=2, priority=cls, tenant=tenant)
    return r


def test_priority_admission_interactive_first():
    sched = serving.Scheduler(priority=True)
    be = _queued(1, "best_effort")
    ba = _queued(2, "batch")
    ia = _queued(3, "interactive")
    for r in (be, ba, ia):
        sched.submit(r)
    order = sched._admission_order()
    assert order == [ia, ba, be]
    admitted, _ = sched.admit(2, now=time.perf_counter())
    assert admitted == [ia, ba]


def test_wfq_tenant_fairness_and_weights():
    """Within a class, tenants round-robin: a flood from tenant A cannot
    starve tenant B; a weight-2 tenant gets two slots per rotation."""
    sched = serving.Scheduler(priority=True)
    a = [_queued(10 + i, tenant="A") for i in range(4)]
    b = [_queued(30 + i, tenant="B") for i in range(2)]
    for r in a[:2] + b[:1] + a[2:] + b[1:]:   # A,A,B,A,A,B arrival
        sched.submit(r)
    order = sched._admission_order()
    assert order[:4] == [a[0], b[0], a[1], b[1]]  # interleaved
    # weights: A earns 2 pops per rotation
    sched2 = serving.Scheduler(priority=True,
                               tenant_weights={"A": 2})
    for r in a[:2] + b[:1] + a[2:] + b[1:]:
        sched2.submit(r)
    order2 = sched2._admission_order()
    assert order2[:3] == [a[0], a[1], b[0]]
    # the rotation pointer survives admissions: after serving A's credit,
    # the next boundary starts at B
    admitted, _ = sched2.admit(2, now=time.perf_counter())
    assert admitted == [a[0], a[1]]
    assert sched2._admission_order()[0] == b[0]


def test_engine_serves_interactive_before_earlier_best_effort():
    eng = _engine(num_slots=1, priority=True)
    blocker = serving.Request(np.arange(3, 8), max_new_tokens=6)
    be = serving.Request(np.arange(1, 6), max_new_tokens=3,
                         priority="best_effort")
    ia = serving.Request(np.arange(2, 7), max_new_tokens=3,
                         priority="interactive")
    eng.submit(blocker)
    eng.step()
    eng.submit(be)       # arrives FIRST
    eng.submit(ia)       # but outranks it
    res = eng.run()
    assert res[ia.request_id].ttft < res[be.request_id].ttft
    # both still bitwise (admission order never changes content)
    assert res[be.request_id].tokens == _ref(be.prompt, 3)
    assert res[ia.request_id].tokens == _ref(ia.prompt, 3)


def test_class_default_deadline_applied_in_priority_mode():
    paddle.set_flags({"FLAGS_serving_class_deadline_interactive": 7.5})
    eng = _engine(priority=True)
    r = serving.Request(np.arange(1, 5), max_new_tokens=1,
                        priority="interactive")
    eng.submit(r)
    assert r.deadline_s == 7.5
    # explicit deadlines win; flags-off engines never stamp
    r2 = serving.Request(np.arange(1, 5), max_new_tokens=1,
                         priority="interactive", deadline_s=1.0)
    eng.submit(r2)
    assert r2.deadline_s == 1.0
    eng_off = _engine()
    r3 = serving.Request(np.arange(2, 6), max_new_tokens=1,
                         priority="interactive")
    eng_off.submit(r3)
    assert r3.deadline_s is None
    eng.run()
    eng_off.run()


# ---------------------------------------------------------------------------
# preemptive admission


def test_preemption_evicts_best_effort_bitwise_replay():
    """A deadline-at-risk interactive evicts the running best_effort; the
    victim requeues at its ORIGINAL arrival and its replay is bitwise."""
    paddle.set_flags({"FLAGS_serving_preempt_margin_s": 60.0})
    eng = _engine(num_slots=1, priority=True)
    victim = serving.Request(np.arange(1, 6), max_new_tokens=8,
                             priority="best_effort")
    eng.submit(victim)
    for _ in range(3):
        eng.step()
    assert victim.tokens                      # mid-flight, tokens streamed
    urgent = serving.Request(np.arange(2, 7), max_new_tokens=2,
                             priority="interactive", deadline_s=50.0)
    eng.submit(urgent)
    res = eng.run()
    c = smetrics.serving_counters()
    assert c["preempted"] == 1
    assert res[urgent.request_id].finish_reason == "length"
    assert res[victim.request_id].tokens == _ref(victim.prompt, 8)
    assert res[victim.request_id].finish_reason == "length"
    # exactly one TTFT sample each despite the victim's round trip
    assert len(smetrics._ttft) == 2


def test_no_preemption_without_deadline_risk():
    """Queued interactive WITHOUT a deadline (or with ample slack) never
    evicts anyone — preemption is deadline-driven, not class-driven."""
    paddle.set_flags({"FLAGS_serving_preempt_margin_s": 0.01})
    eng = _engine(num_slots=1, priority=True)
    victim = serving.Request(np.arange(1, 6), max_new_tokens=6,
                             priority="best_effort")
    eng.submit(victim)
    eng.step()
    eng.submit(serving.Request(np.arange(2, 7), max_new_tokens=2,
                               priority="interactive"))          # no deadline
    eng.submit(serving.Request(np.arange(3, 8), max_new_tokens=2,
                               priority="interactive",
                               deadline_s=3600.0))               # huge slack
    eng.run()
    assert smetrics.serving_counters()["preempted"] == 0


def test_preemption_never_evicts_same_or_better_class():
    paddle.set_flags({"FLAGS_serving_preempt_margin_s": 60.0})
    eng = _engine(num_slots=1, priority=True)
    first = serving.Request(np.arange(1, 6), max_new_tokens=6,
                            priority="interactive")
    eng.submit(first)
    eng.step()
    eng.submit(serving.Request(np.arange(2, 7), max_new_tokens=2,
                               priority="interactive", deadline_s=50.0))
    eng.run()
    assert smetrics.serving_counters()["preempted"] == 0


# ---------------------------------------------------------------------------
# load shedding


def _overload_engine(**kw):
    paddle.set_flags({"FLAGS_serving_shed_window": 2})
    return _engine(num_slots=1, priority=True, shed=True, max_queue=8, **kw)


def test_shed_lowest_class_with_retry_after():
    eng = _overload_engine()
    reqs = [serving.Request(np.arange(1, 6), max_new_tokens=4,
                            priority="interactive" if i == 0
                            else "best_effort")
            for i in range(8)]
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    c = smetrics.serving_counters()
    assert c["shed"] > 0
    assert c["shed_queue_wait_s"] > 0         # refused work stays visible
    res = eng.run()
    shed = [r for r in res.values() if r.finish_reason == serving.SHED]
    assert shed
    assert all(r.retry_after is not None and r.retry_after > 0
               for r in shed)
    assert all(r.priority != "interactive" for r in shed)
    # the interactive request survived the overload
    assert res[reqs[0].request_id].finish_reason in ("stop", "length")
    assert "slo:" in smetrics.serving_summary()


def test_shed_refuses_new_best_effort_while_latched_then_recovers():
    eng = _overload_engine()
    for i in range(8):
        eng.submit(serving.Request(np.arange(1, 6), max_new_tokens=4,
                                   priority="best_effort"))
    for _ in range(3):
        eng.step()
    assert eng._shed.shedding
    with pytest.raises(ShedError) as ei:
        eng.submit(serving.Request(np.arange(9, 14), max_new_tokens=2,
                                   priority="best_effort"))
    assert ei.value.retry_after > 0
    assert ei.value.qsize is not None and ei.value.max_queue == 8
    # batch/interactive still accepted while best_effort sheds
    ok = serving.Request(np.arange(2, 7), max_new_tokens=2,
                         priority="batch")
    eng.submit(ok)
    eng.run()
    assert not eng._shed.shedding             # drained: latch released
    late = serving.Request(np.arange(3, 8), max_new_tokens=2,
                           priority="best_effort")
    eng.submit(late)
    res = eng.run()
    assert res[late.request_id].finish_reason in ("stop", "length")


def test_queue_wait_recorded_for_expired():
    eng = _engine(num_slots=1)
    blocker = serving.Request(np.arange(3, 8), max_new_tokens=8)
    doomed = serving.Request(np.arange(1, 6), max_new_tokens=2,
                             deadline_s=0.001)
    eng.submit(blocker)
    eng.step()
    eng.submit(doomed)
    time.sleep(0.01)
    res = eng.run()
    assert res[doomed.request_id].finish_reason == serving.EXPIRED
    c = smetrics.serving_counters()
    assert c["expired"] == 1
    assert c["expired_queue_wait_s"] > 0
    assert c["expired_queue_wait_mean"] > 0


# ---------------------------------------------------------------------------
# slo.py policy units


def test_token_bucket_exact_hints():
    tb = TokenBucket(rate=2.0, burst=2)
    assert tb.take(now=10.0) == 0.0
    assert tb.take(now=10.0) == 0.0
    wait = tb.take(now=10.0)                   # burst spent
    assert wait == pytest.approx(0.5)          # 1 token / 2 per s
    assert tb.take(now=10.5) == 0.0            # accrued exactly on time
    assert tb.take(now=10.5) == pytest.approx(0.5)


def test_drain_rate_retry_after():
    dr = DrainRate(alpha=1.0)
    dr.observe(0, now=0.0)
    dr.observe(10, now=1.0)                    # 10 resolved/s
    assert dr.rate == pytest.approx(10.0)
    assert dr.retry_after(20) == pytest.approx(2.0)
    assert dr.retry_after(-5) == 0.05          # floor
    assert DrainRate().retry_after(1000, ceil=60.0) == 60.0


def test_autoscaler_hysteresis_and_cooldown():
    a = Autoscaler(min_replicas=1, max_replicas=3, up_queue=4.0,
                   down_queue=0.5, up_occupancy=0.9, down_occupancy=0.2,
                   window=2, cooldown_s=10.0)
    # one hot sample: below window, no action
    assert a.decide(1, 10, 2, 2, now=0.0) is None
    assert a.decide(1, 10, 2, 2, now=1.0) == "grow"
    # cooldown: still hot, but too soon
    assert a.decide(2, 20, 4, 4, now=2.0) is None
    assert a.decide(2, 20, 4, 4, now=5.0) is None
    assert a.decide(2, 20, 4, 4, now=12.0) == "grow"
    # dead band resets both streaks
    assert a.decide(3, 6, 3, 6, now=30.0) is None
    assert a.decide(3, 0, 0, 6, now=31.0) is None
    assert a.decide(3, 0, 0, 6, now=32.0) == "shrink"
    # bounds respected
    assert a.decide(1, 0, 0, 2, now=60.0) is None    # min_replicas
    b = Autoscaler(max_replicas=1, up_queue=1.0, window=1, cooldown_s=0.0)
    assert b.decide(1, 10, 2, 2, now=0.0) is None    # max_replicas


def test_autoscaler_ttft_slo_trigger():
    a = Autoscaler(min_replicas=1, max_replicas=2, up_queue=1e9,
                   up_occupancy=2.0, ttft_slo_s=0.1, window=1,
                   cooldown_s=0.0)
    assert a.decide(1, 0, 0, 2, ttft_p99=0.05, now=0.0) is None
    assert a.decide(1, 0, 0, 2, ttft_p99=0.5, now=1.0) == "grow"


def test_arrival_surge_deterministic_and_inactive_zero():
    s1 = fi.ArrivalSurge(base_rate=0.5, surge_rate=4.0, surge_start=2,
                         surge_steps=4, total_steps=16, seed=3)
    s2 = fi.ArrivalSurge(base_rate=0.5, surge_rate=4.0, surge_start=2,
                         surge_steps=4, total_steps=16, seed=3)
    assert s1.counts.tolist() == s2.counts.tolist()
    assert s1.in_surge(3) and not s1.in_surge(6)
    assert s1.arrivals(999) == 0
    fi.deactivate()
    assert fi.surge_arrivals(0) == 0          # no plan: zero-cost zero
    with fi.inject(fi.FaultPlan(surge=s1)):
        total = sum(fi.surge_arrivals(i) for i in range(16))
    assert total == int(s1.counts.sum())
    assert fi.stats()["surged_arrivals"] == total


# ---------------------------------------------------------------------------
# hot weight swap


def test_swap_params_bitwise_no_retrace_cache_invalidated():
    eng = _engine(num_slots=2)
    r1 = serving.Request(np.arange(1, 6), max_new_tokens=3)
    out_v0 = eng.run([r1])[r1.request_id]
    assert out_v0.params_version == 0
    traces = smetrics.serving_counters()["paged_traces"]
    eng.swap_params(_params(1), version=7)
    # SAME prompt: a stale prefix-cache hit would serve v0 KV
    r2 = serving.Request(np.arange(1, 6), max_new_tokens=3)
    res = eng.run([r2])[r2.request_id]
    assert res.tokens == _ref(r2.prompt, 3, params_seed=1)
    assert res.params_version == 7
    assert smetrics.serving_counters()["paged_traces"] == traces
    assert smetrics.serving_counters()["weight_swaps"] == 1


def test_swap_params_guards():
    eng = _engine(num_slots=1)
    eng.submit(serving.Request(np.arange(1, 6), max_new_tokens=4))
    eng.step()
    with pytest.raises(RuntimeError, match="non-idle"):
        eng.swap_params(_params(1))
    eng.run()
    bad = jax.tree_util.tree_map(lambda x: x[..., :1], _params(1))
    with pytest.raises(ValueError):
        eng.swap_params(bad)


def test_snapshot_carries_version_and_mismatch_rejected(tmp_path):
    eng = _engine(num_slots=1)
    eng.submit(serving.Request(np.arange(1, 8), max_new_tokens=8))
    for _ in range(3):
        eng.step()
    snap = eng.state_dict()
    assert snap["meta"]["params_version"] == 0
    # an upgraded engine must NOT resume old-version KV mid-stream
    eng2 = _engine(num_slots=1)
    eng2.swap_params(_params(1), version=1)
    with pytest.raises(ValueError, match="snapshot meta"):
        eng2.load_state_dict(snap)
    # same-version engine restores and finishes bitwise
    eng3 = _engine(num_slots=1)
    eng3.load_state_dict(snap)
    res = eng3.run()
    (only,) = res.values()
    assert only.tokens == _ref(np.arange(1, 8), 8)


def test_rolling_restart_new_params_single_version_zero_drops():
    """Upgrade under load: zero drops, every result single-version
    bitwise, fleet converges, future respawns serve the new weights."""
    def factory():
        return _engine(num_slots=2, max_queue=64)

    sup = serving.ServingSupervisor(factory, num_replicas=2)
    rng = np.random.default_rng(5)
    reqs = []
    for i in range(10):
        kw = ({"do_sample": True, "temperature": 0.8, "top_p": 0.9,
               "seed": 40 + i} if i % 2 else {})
        reqs.append(serving.Request(rng.integers(0, 97, 4 + i % 3),
                                    max_new_tokens=3 + i % 3, **kw))
    for r in reqs:
        sup.submit(r)
    for _ in range(2):
        sup.step()
    sup.rolling_restart(new_params=_params(1))
    res = sup.run()
    assert len(res) == len(reqs)
    for r in reqs:
        out = res[r.request_id]
        assert out.finish_reason in ("stop", "length")
        kw = ({"do_sample": True, "temperature": r.temperature,
               "top_p": r.top_p, "seed": r.seed} if r.do_sample else {})
        assert out.tokens == _ref(r.prompt, r.max_new_tokens,
                                  params_seed=out.params_version, **kw), \
            f"request {r.request_id} not single-version consistent"
    c = smetrics.serving_counters()
    assert c["dropped"] == 0
    assert c["rolling_restarts"] == 1
    assert sup.telemetry()["params_version"] == 1
    for rep in sup._replicas:
        assert rep.engine.params_version == 1
    # a crash respawn AFTER the upgrade serves the new weights too
    sup._on_failure(sup._replicas[0], RuntimeError("boom"))
    assert sup._replicas[0].engine.params_version == 1


# ---------------------------------------------------------------------------
# supervisor: autoscale, rate limits, satellite fixes


def _factory():
    return _engine(num_slots=2, max_queue=64)


def test_supervisor_autoscale_grow_and_shrink():
    sup = serving.ServingSupervisor(
        _factory, num_replicas=1,
        autoscale=Autoscaler(min_replicas=1, max_replicas=3, up_queue=1.0,
                             down_queue=0.5, down_occupancy=0.3, window=1,
                             cooldown_s=0.0))
    reqs = [serving.Request(np.arange(1, 6) + i, max_new_tokens=4)
            for i in range(12)]
    for r in reqs:
        sup.submit(r)
    sup.step()
    assert sup.alive_replicas > 1             # grew under backlog
    res = sup.run()
    assert len(res) == len(reqs)
    for _ in range(10):                       # idle: shrinks back to min
        sup.step()
    assert sup.alive_replicas == 1
    c = smetrics.serving_counters()
    assert c["scale_ups"] >= 1 and c["scale_downs"] >= 1
    assert c["dropped"] == 0
    # retired replicas stay indexed (owner bookkeeping never shifts)
    assert len(sup._replicas) > sup.alive_replicas


def test_supervisor_tenant_rate_limit():
    sup = serving.ServingSupervisor(_factory, num_replicas=1,
                                    tenant_rate=0.001, tenant_burst=2)
    for _ in range(2):
        sup.submit(serving.Request(np.arange(1, 6), max_new_tokens=1,
                                   tenant="noisy"))
    with pytest.raises(ShedError) as ei:
        sup.submit(serving.Request(np.arange(1, 6), max_new_tokens=1,
                                   tenant="noisy"))
    assert ei.value.retry_after > 0
    # fleet-wide fields ride along; other tenants unaffected
    assert ei.value.max_queue == 64
    sup.submit(serving.Request(np.arange(1, 6), max_new_tokens=1,
                               tenant="quiet"))
    assert smetrics.serving_counters()["rate_limited"] == 1
    sup.run()


def test_submit_never_routes_to_draining_replica():
    """Regression (satellite): the spill check used to compare only queue
    depth, so a replica mid-drain (rolling restart) could be picked and
    the submit would explode with EngineStoppedError."""
    sup = serving.ServingSupervisor(_factory, num_replicas=2)
    sup._replicas[0].engine.drain()           # mid-rolling-restart state
    r = sup.submit(serving.Request(np.arange(1, 6), max_new_tokens=2))
    assert sup._owner[r.request_id] == 1      # routed around the drain
    res = sup.run()
    assert res[r.request_id].finish_reason in ("stop", "length")
    # with EVERY replica draining, submit reports no live replica instead
    # of exploding inside a drained engine
    sup2 = serving.ServingSupervisor(_factory, num_replicas=1)
    sup2._replicas[0].engine.drain()
    with pytest.raises(serving.EngineStoppedError):
        sup2.submit(serving.Request(np.arange(1, 6), max_new_tokens=2))


def test_queue_full_error_reports_fleet_totals():
    sup = serving.ServingSupervisor(
        lambda: _engine(num_slots=1, max_queue=2), num_replicas=2)
    for i in range(4):
        sup.submit(serving.Request(np.arange(1, 6) + i, max_new_tokens=2))
    with pytest.raises(serving.QueueFullError) as ei:
        sup.submit(serving.Request(np.arange(9, 14), max_new_tokens=2))
    assert ei.value.qsize == 4                # fleet-wide, not last-probed
    assert ei.value.max_queue == 4
    sup.run()


def test_supervisor_spills_past_shedding_replica_fleet_shed_error():
    """A shed-latched replica is probed, not trial-submitted: best_effort
    work spills to a healthy replica; only when EVERY candidate is
    latched/full does ShedError surface — with fleet-wide totals and the
    largest drain hint (never a replica-local engine ShedError)."""
    sup = serving.ServingSupervisor(
        lambda: _engine(num_slots=2, shed=True, max_queue=8),
        num_replicas=2)
    sup._replicas[0].engine._shed.shedding = True
    r = sup.submit(serving.Request(np.arange(1, 6), max_new_tokens=2,
                                   priority="best_effort"))
    assert sup._owner[r.request_id] == 1      # spilled past the latch
    sup._replicas[1].engine._shed.shedding = True
    with pytest.raises(ShedError) as ei:
        sup.submit(serving.Request(np.arange(2, 7), max_new_tokens=2,
                                   priority="best_effort"))
    assert ei.value.max_queue == 16           # fleet-wide, both replicas
    assert ei.value.retry_after > 0
    # batch class is not shed-refused: still routable while latched
    ok = sup.submit(serving.Request(np.arange(3, 8), max_new_tokens=2,
                                    priority="batch"))
    sup._replicas[0].engine._shed.shedding = False
    sup._replicas[1].engine._shed.shedding = False
    res = sup.run()
    assert res[ok.request_id].finish_reason in ("stop", "length")


def test_preemption_seats_the_at_risk_request_not_wfq_next():
    """The freed slot goes to the deadline-holder the eviction was FOR —
    not to whoever the deadline-blind WFQ rotation would pick next."""
    paddle.set_flags({"FLAGS_serving_preempt_margin_s": 60.0})
    eng = _engine(num_slots=1, priority=True)
    victim = serving.Request(np.arange(1, 6), max_new_tokens=8,
                             priority="best_effort")
    eng.submit(victim)
    eng.step()
    # same class, EARLIER arrival, no deadline: WFQ/FCFS would pick this
    calm = serving.Request(np.arange(2, 7), max_new_tokens=2,
                           priority="interactive", tenant="A")
    eng.submit(calm)
    urgent = serving.Request(np.arange(3, 8), max_new_tokens=2,
                             priority="interactive", tenant="B",
                             deadline_s=50.0)
    eng.submit(urgent)
    eng.step()
    # seated by the preemption (and already producing tokens — the fused
    # step can finish a short request within the boundary); the WFQ-next
    # same-class request is still waiting
    assert urgent.tokens and urgent.state in (serving.RUNNING,
                                              serving.FINISHED)
    assert calm.state == serving.QUEUED and not calm.tokens
    res = eng.run()
    assert smetrics.serving_counters()["preempted"] == 1
    for r in (victim, calm, urgent):
        assert res[r.request_id].tokens == \
            _ref(r.prompt, r.max_new_tokens)


def test_weight_swaps_counts_upgrades_not_respawns():
    """One upgrade on N replicas = N swaps in the ledger; later crash
    respawns RE-apply the live weights without inflating the audit
    trail."""
    sup = serving.ServingSupervisor(_factory, num_replicas=2)
    sup.rolling_restart(new_params=_params(1))
    assert smetrics.serving_counters()["weight_swaps"] == 2
    sup._on_failure(sup._replicas[0], RuntimeError("crash"))
    assert sup._replicas[0].engine.params_version == 1
    assert smetrics.serving_counters()["weight_swaps"] == 2   # unchanged


def test_capacity_probe_never_evicts_prefix_cache():
    """_capacity_for's paged probe answers from free + reclaimable counts
    without allocating: a transient probe must not churn the LRU cache
    (pool.try_alloc would evict entries to satisfy it)."""
    eng = _engine(num_slots=2, num_pages=13)    # tight pool (1 is trash)
    warm = serving.Request(np.arange(1, 17), max_new_tokens=2)
    eng.run([warm])                             # registers prefix pages
    pool = eng.pool
    entries = pool.cache_entries
    assert entries > 0
    free0 = pool.free_count
    big = serving.Request(np.arange(30, 70), max_new_tokens=40)
    probe = eng._capacity_for(big)              # needs cache reclaim space
    assert pool.cache_entries == entries        # cache untouched
    assert pool.free_count == free0             # nothing allocated
    # and the probe agrees with what a real reservation could do
    assert probe == pool.can_alloc(
        serving.pages_for(big.prompt_len + big.max_new_tokens,
                          eng.page_size))


def test_token_bucket_map_bounded():
    tb = TokenBucket(rate=1.0, burst=2)
    assert tb.idle_full(now=0.0)                # untouched = fresh
    tb.take(now=0.0)
    assert not tb.idle_full(now=0.5)
    assert tb.idle_full(now=5.0)                # refilled to burst
    sup = serving.ServingSupervisor(_factory, num_replicas=1,
                                    tenant_rate=100.0, tenant_burst=2)
    for i in range(1100):                       # rotating tenant ids
        sup._buckets[f"t{i}"] = TokenBucket(100.0, 2)
    sup._rate_limit(serving.Request(np.arange(1, 4), tenant="live"))
    assert len(sup._buckets) <= 2               # stale buckets swept


def test_shed_queue_wait_mean_counts_only_queued_sheds():
    """Up-front ShedError refusals bump 'shed' but carry no queue wait;
    the mean divides by the recorded-wait count so it is not diluted."""
    smetrics.observe_queue_wait(0.2, "shed")
    smetrics.bump("shed", 5)                  # 4 up-front refusals ride on
    c = smetrics.serving_counters()
    assert c["shed_queue_waits"] == 1
    assert c["shed_queue_wait_mean"] == pytest.approx(0.2)


# ---------------------------------------------------------------------------
# the chaos ladder (quick rungs tier-1, p99 gate slow)


def _load_smoke():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tools_slo_smoke",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools_slo_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_slo_smoke_quick_ladder():
    """tools_slo_smoke's structural rungs: surge→shed→recover,
    upgrade-under-load (single-version bitwise), kill-during-surge."""
    smoke = _load_smoke()
    out = smoke.run_ladder(full=False)
    for rung, info in out.items():
        assert info["ok"], (rung, info)


@pytest.mark.slow
def test_slo_smoke_p99_gate():
    """The timing-sensitive gate: interactive-class p99 TTFT held through
    surge + hot weight swap + replica kill."""
    smoke = _load_smoke()
    info = smoke.rung_p99_held()
    assert info["ok"], info
