"""Many-model serving: per-slot LoRA-class adapters on one paged engine
(serving/adapters.py).

The exactness contract is the tentpole gate: in a MIXED-adapter batch
(ids interleaved, base id 0 included) every slot's token stream is
bitwise identical to a solo ``generate_from_params(adapters=...)`` run
of its adapter — greedy AND sampled, for any admission order, single-
chip and mp in {2, 4}. Plus:

  * the two-executable steady state holds WITH adapters on
    (``paged_traces == 2``), and adapter hot-load / evict / in-place
    swap are content-only rewrites — ZERO additional traces;
  * adapter ops never flush the shared-base prefix cache (base traffic
    keys prefix pages by tokens alone; adapted requests' keys carry
    their adapter id + content version, since the out/up/down deltas
    feed the residual stream later layers' KV is computed from), while
    a base ``swap_params`` keeps its full flush — both regression-gated;
  * typed ``UnknownAdapterError`` at construction and submit; requests
    bound to a NON-RESIDENT adapter wait at admission (strict in-order)
    until a load, and mutating an adapter bound to a RUNNING slot is
    refused;
  * WFQ fairness lanes by ADAPTER on an adapter engine
    (``Scheduler(lane_key=)``), and ``FLAGS_serving_tenant_adapters``
    maps tenants to default adapters;
  * kill-and-resume carries the resident adapter set and per-slot
    bindings bitwise; the supervisor's fleet-level adapter ops survive
    replica death and rolling restarts;
  * residency/delta-bytes/token-share land in the metrics ledger and
    the ``adapters:`` serving_summary segment.
"""
import numpy as np
import pytest
import jax

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu.models.generation import generate_from_params
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.gpt_hybrid import init_gpt_params
from paddle_tpu.serving import metrics as smetrics
from paddle_tpu.serving.adapters import (
    AdapterRegistry, AdapterSpec, UnknownAdapterError,
)
from paddle_tpu.serving.slo import resolve_tenant_adapters
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
    kw.setdefault("adapter_slots", 3)
    kw.setdefault("adapter_rank", 4)
    return serving.Engine(params=_params(), config=CFG, **kw)


def _delta(seed, rank=4, targets=("out_w", "up_w", "down_w")):
    """A deterministic low-rank delta tree (A [L,K,r], B [L,r,F])."""
    rng = np.random.default_rng(seed)
    H, I = CFG.hidden_size, 4 * CFG.hidden_size
    dims = {"out_w": (H, H), "up_w": (H, I), "down_w": (I, H)}
    return {t: (rng.standard_normal(
                    (CFG.num_layers, dims[t][0], rank)).astype(np.float32)
                * 0.05,
                rng.standard_normal(
                    (CFG.num_layers, rank, dims[t][1])).astype(np.float32)
                * 0.05)
            for t in targets}


def _load_std(eng):
    """Load the standard 2-adapter palette; returns the engine."""
    eng.load_adapter(1, _delta(1), alpha=8.0)
    eng.load_adapter(2, _delta(2), alpha=8.0)
    return eng


def _ref_tokens(prompt, max_new, adapters=None, **kw):
    out = np.asarray(generate_from_params(
        _params(), np.asarray(prompt)[None], CFG, max_new_tokens=max_new,
        adapters=adapters, **kw)._data)
    return out[0, len(prompt):].tolist()


def _check_bitwise(eng, reqs, results, **ref_kw):
    """Every request's stream must equal its adapter's SOLO reference."""
    slabs = eng.adapters.device_slabs()
    for r in reqs:
        aid = r.adapter or 0
        kw = dict(ref_kw)
        if r.do_sample:
            kw.update(do_sample=True, temperature=r.temperature,
                      top_p=r.top_p, seed=r.seed)
        ref = _ref_tokens(r.prompt, r.max_new_tokens,
                          adapters=(aid, slabs), **kw)
        got = results[r.request_id].tokens
        assert got == ref[:len(got)] and got, \
            f"adapter {aid} request {r.request_id}: {got} != {ref}"


_SHAPES = ((3, 4), (5, 6), (9, 4), (13, 6), (21, 5), (4, 4))


def _mixed_requests(order, rng, sampled=False):
    reqs = []
    for i, aid in enumerate(order):
        plen, mnt = _SHAPES[i % len(_SHAPES)]
        kw = {}
        if sampled:
            kw = dict(do_sample=True, temperature=0.9, top_p=0.9,
                      seed=100 + i)
        reqs.append(serving.Request(rng.integers(0, CFG.vocab_size, plen),
                                    max_new_tokens=mnt, adapter=aid, **kw))
    return reqs


# ---------------------------------------------------------------------------
# tentpole: mixed-adapter bitwise exactness


@pytest.mark.parametrize("sampled", [False, True])
def test_mixed_adapter_batch_bitwise_two_orders(sampled):
    """A batch interleaving base + two adapters matches each adapter's
    SOLO reference bitwise — greedy and sampled, two admission orders."""
    for order in ((0, 1, 2, 1, 0, 2), (2, 0, 1, 0, 2, 1)):
        eng = _load_std(_engine())
        reqs = _mixed_requests(order, np.random.default_rng(7),
                               sampled=sampled)
        results = eng.run(reqs)
        _check_bitwise(eng, reqs, results)


def test_batch_composition_invariance():
    """The same request decodes identically whether its batch neighbors
    run the base, its own adapter, or a different one — the row-
    independence guarantee of the where-composed delta epilogue."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, CFG.vocab_size, 7)
    outs = []
    for neighbors in ((0, 0), (1, 2), (2, 2)):
        eng = _load_std(_engine())
        probe = serving.Request(prompt, max_new_tokens=6, adapter=1)
        reqs = [probe] + [
            serving.Request(rng.integers(0, CFG.vocab_size, 5),
                            max_new_tokens=6, adapter=a) for a in neighbors]
        outs.append(eng.run(reqs)[probe.request_id].tokens)
    assert outs[0] == outs[1] == outs[2], outs


# ---------------------------------------------------------------------------
# zero-retrace gates


def test_two_executables_with_adapters_and_zero_retrace_ops():
    """paged_traces freezes at 2 with adapters on, and hot load / evict /
    swap add ZERO traces — adapter ids are traced operands, adapter ops
    content-only rewrites. (num_slots=6 is unique in the suite:
    executables are shared ACROSS engines per shape, so only fresh
    shapes show warmup traces.)"""
    profiler.reset_serving_counters()
    eng = _load_std(_engine(num_slots=6))
    rng = np.random.default_rng(11)
    eng.run(_mixed_requests((0, 1, 2, 1), rng))
    assert smetrics.serving_counters()["paged_traces"] == 2
    # hot ops while warm: load a third adapter, swap one, evict another
    eng.load_adapter(3, _delta(3), alpha=4.0)
    eng.swap_adapter(1, _delta(41), alpha=8.0)
    eng.evict_adapter(2)
    eng.load_adapter(2, _delta(42), alpha=8.0)
    results = eng.run(_mixed_requests((3, 1, 2, 0, 3), rng))
    assert results
    c = smetrics.serving_counters()
    assert c["paged_traces"] == 2, \
        f"adapter ops retraced: paged_traces={c['paged_traces']}"
    assert c["adapter_loads"] == 4 and c["adapter_evicts"] == 1 \
        and c["adapter_swaps"] == 1
    # the post-op streams serve the NEW content, still bitwise
    more = _mixed_requests((1, 3), rng)
    _check_bitwise(eng, more, eng.run(more))


def test_mixed_adapter_run_still_bitwise_after_swap():
    """swap_adapter changes the bits a NEW request decodes under;
    versions stamp which content each result saw."""
    eng = _load_std(_engine())
    prompt = np.arange(2, 9)
    r1 = serving.Request(prompt, max_new_tokens=6, adapter=1)
    before = eng.run([r1])[r1.request_id]
    v2 = eng.swap_adapter(1, _delta(99), alpha=8.0)
    r2 = serving.Request(prompt, max_new_tokens=6, adapter=1)
    after = eng.run([r2])[r2.request_id]
    slabs = eng.adapters.device_slabs()
    assert after.tokens == _ref_tokens(prompt, 6, adapters=(1, slabs))[
        :len(after.tokens)]
    assert before.adapter_version != after.adapter_version
    assert after.adapter_version == v2
    assert before.adapter == after.adapter == 1


# ---------------------------------------------------------------------------
# prefix-cache invalidation scoping (satellite 1)


def test_adapter_ops_preserve_prefix_cache_base_swap_flushes():
    """Adapter load/evict/swap must NOT flush shared-base prefix pages —
    base traffic keys pages by tokens alone, adapted requests' keys are
    salted with (adapter id, content version) so every hit is content-
    exact — while a base-weight swap_params keeps the full flush."""
    eng = _load_std(_engine())
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, CFG.vocab_size, 17)   # > 2 pages: cacheable
    r = serving.Request(prompt, max_new_tokens=4, adapter=0)
    eng.run([r])
    keys_before = set(eng.pool._cache)
    assert keys_before, "run left no prefix-cache entries; gate is vacuous"
    eng.load_adapter(3, _delta(3))
    eng.swap_adapter(1, _delta(31), alpha=8.0)
    eng.evict_adapter(3)
    assert set(eng.pool._cache) == keys_before, \
        "an adapter op flushed shared-base prefix pages"
    # the preserved BASE pages are reused by later base traffic, exactly
    profiler.reset_serving_counters()
    rb = serving.Request(prompt, max_new_tokens=4, adapter=0)
    _check_bitwise(eng, [rb], eng.run([rb]))
    assert smetrics.serving_counters()["prefix_hits"] >= 1, \
        "base prefix reuse never fired after adapter ops"
    # an ADAPTED request must NOT hit base pages (its prompt KV depends
    # on its delta bits through the residual stream) — and stays exact
    profiler.reset_serving_counters()
    r1 = serving.Request(prompt, max_new_tokens=4, adapter=1)
    _check_bitwise(eng, [r1], eng.run([r1]))
    assert smetrics.serving_counters()["prefix_hits"] == 0, \
        "adapter request consumed base-keyed prefix pages"
    # ... but DOES hit its own salted entries on a repeat, exactly
    profiler.reset_serving_counters()
    r1b = serving.Request(prompt, max_new_tokens=4, adapter=1)
    _check_bitwise(eng, [r1b], eng.run([r1b]))
    assert smetrics.serving_counters()["prefix_hits"] >= 1, \
        "same-adapter prefix reuse never fired"
    # a swap bumps the content version: the stale entries are simply
    # unreachable (no flush), and the post-swap stream is exact
    cached = len(eng.pool._cache)
    eng.swap_adapter(1, _delta(77), alpha=8.0)
    assert len(eng.pool._cache) == cached, "swap_adapter flushed the cache"
    profiler.reset_serving_counters()
    r1c = serving.Request(prompt, max_new_tokens=4, adapter=1)
    _check_bitwise(eng, [r1c], eng.run([r1c]))
    assert smetrics.serving_counters()["prefix_hits"] == 0, \
        "post-swap request hit a pre-swap (stale-content) prefix entry"
    # the full flush is scoped to BASE-weight swaps: still there
    eng.swap_params(_params())
    assert not eng.pool._cache, \
        "swap_params no longer flushes the prefix cache"


# ---------------------------------------------------------------------------
# typed errors, residency-blocking admission, in-use protection


def test_unknown_adapter_typed_errors():
    eng = _engine(adapter_slots=2)
    # out of capacity at submit; error names the id
    with pytest.raises(UnknownAdapterError) as ei:
        eng.submit(serving.Request([1, 2, 3], max_new_tokens=2, adapter=7))
    assert ei.value.adapter_id == 7
    # negative id fails Request validation itself
    with pytest.raises(UnknownAdapterError):
        serving.Request([1, 2, 3], adapter=-1)
    # an adapter-less engine refuses adapter traffic, typed
    plain = serving.Engine(params=_params(), config=CFG, num_slots=2,
                           max_seq_len=96, page_size=8, prefill_chunk=8)
    with pytest.raises(UnknownAdapterError):
        plain.submit(serving.Request([1, 2, 3], max_new_tokens=2, adapter=1))
    # tenant mapping outside capacity is a construction-time error
    with pytest.raises(UnknownAdapterError):
        _engine(adapter_slots=2, tenant_adapters={"acme": 9})


def test_construction_gates():
    with pytest.raises(ValueError, match="speculative"):
        _engine(speculate_k=2)
    eng = _engine()
    with pytest.raises(ValueError, match="single-role"):
        eng.set_role("prefill")
    with pytest.raises(ValueError):
        AdapterSpec(slots=2, rank=0)
    reg = eng.adapters
    with pytest.raises(ValueError, match="qkv_w"):
        reg.load(1, {"qkv_w": _delta(1)["out_w"]})
    with pytest.raises(ValueError, match="rank"):
        reg.load(1, _delta(1, rank=9))     # exceeds the configured max 4


def test_non_resident_adapter_blocks_admission_until_load():
    """A request bound to a non-resident adapter queues and WAITS at
    admission (typed counter ticks); a hot load admits it at the next
    boundary — and its stream is exact."""
    profiler.reset_serving_counters()
    eng = _engine()
    req = serving.Request(np.arange(5, 12), max_new_tokens=5, adapter=2)
    eng.submit(req)
    for _ in range(3):
        eng.step()
    assert req.slot is None and eng.queue_depth == 1, \
        "non-resident adapter request was admitted"
    assert smetrics.serving_counters()["adapter_admit_blocked"] >= 1
    eng.load_adapter(2, _delta(2), alpha=8.0)
    results = eng.run()
    _check_bitwise(eng, [req], results)


def test_mutating_bound_adapter_refused_until_slot_frees():
    eng = _load_std(_engine())
    req = serving.Request(np.arange(3, 8), max_new_tokens=12, adapter=1)
    eng.submit(req)
    while req.slot is None:
        eng.step()
    for fn in (lambda: eng.evict_adapter(1),
               lambda: eng.swap_adapter(1, _delta(9)),
               lambda: eng.load_adapter(1, _delta(9))):
        with pytest.raises(RuntimeError, match="bound to running"):
            fn()
    eng.run()                       # stream finishes, slot frees
    eng.swap_adapter(1, _delta(9), alpha=8.0)
    eng.evict_adapter(1)


# ---------------------------------------------------------------------------
# scheduling: WFQ lanes by adapter, tenant default mapping


def test_wfq_lanes_rotate_across_adapters():
    """Scheduler(lane_key=) generalization: admission deficit-round-
    robins across ADAPTER lanes, weights keyed by the lane value (string
    spelling accepted for flag-file weights)."""
    sch = serving.Scheduler(priority=True,
                            tenant_weights={"1": 2},
                            lane_key=lambda r: r.adapter or 0)
    reqs = [serving.Request([1, 2], max_new_tokens=1, adapter=a)
            for a in (1, 1, 1, 2, 2)]
    for r in reqs:
        sch.submit(r)
    admitted, _ = sch.admit(5)
    assert [r.adapter for r in admitted] == [1, 1, 2, 1, 2], \
        "weight-2 lane 1 should serve two per rotation"


def test_wfq_adapter_engine_integration():
    """One hot adapter's burst cannot starve the others: everything
    completes, exactly."""
    eng = _load_std(_engine(priority=True, num_slots=2))
    rng = np.random.default_rng(13)
    reqs = _mixed_requests((1, 1, 1, 1, 2, 0, 2), rng)
    _check_bitwise(eng, reqs, eng.run(reqs))


def test_tenant_default_adapter_mapping():
    eng = _load_std(_engine(tenant_adapters={"acme": 1, "beta": 2}))
    r_acme = serving.Request(np.arange(4, 10), max_new_tokens=5,
                             tenant="acme")
    r_other = serving.Request(np.arange(4, 10), max_new_tokens=5,
                              tenant="nobody")
    r_expl = serving.Request(np.arange(4, 10), max_new_tokens=5,
                             tenant="acme", adapter=2)   # explicit id wins
    results = eng.run([r_acme, r_other, r_expl])
    assert results[r_acme.request_id].adapter == 1
    assert results[r_other.request_id].adapter == 0
    assert results[r_expl.request_id].adapter == 2
    _check_bitwise(eng, [r_acme, r_other, r_expl], results)


def test_resolve_tenant_adapters_flag_spellings():
    assert resolve_tenant_adapters(
        {"FLAGS_serving_tenant_adapters": {"acme": 1}}) == {"acme": 1}
    assert resolve_tenant_adapters(
        {"FLAGS_serving_tenant_adapters": "acme:1, beta:2"}) \
        == {"acme": 1, "beta": 2}
    assert resolve_tenant_adapters({}) == {}
    with pytest.raises(ValueError):
        resolve_tenant_adapters({"FLAGS_serving_tenant_adapters": "acme"})


# ---------------------------------------------------------------------------
# snapshots: kill-and-resume carries the adapter set (satellite 3)


@pytest.mark.parametrize("sampled", [False, True])
def test_kill_resume_carries_adapter_set_bitwise(sampled):
    """Mid-flight kill + restore on a FRESH engine: the resident adapter
    set, per-adapter versions and per-slot bindings ride the snapshot;
    every stream resumes bitwise."""
    eng = _load_std(_engine())
    rng = np.random.default_rng(17)
    reqs = _mixed_requests((1, 0, 2, 1), rng, sampled=sampled)
    for r in reqs:
        eng.submit(r)
    for _ in range(4):
        eng.step()
    assert eng.active_slots, "kill must land mid-traffic"
    state = eng.state_dict()
    pre = eng.pop_results()
    del eng                                  # the "kill"

    restored = _engine()                     # NOTE: no adapters loaded
    restored.load_state_dict(state)
    assert sorted(restored.adapters.resident_ids()) == [1, 2]
    results = restored.run()
    results.update(pre)
    _check_bitwise(restored, reqs, results)


def test_restore_refuses_adapter_capacity_mismatch():
    eng = _load_std(_engine())
    state = eng.state_dict()
    other = _engine(adapter_slots=5)
    with pytest.raises(ValueError, match="adapter"):
        other.load_state_dict(state)


def test_pre_adapter_snapshot_restores_on_adapter_engine_and_back():
    """Back-compat both ways: an adapter-less snapshot restores onto an
    adapter-less engine built from the same factory defaults, and the
    meta['adapters'] field defaults cleanly when absent."""
    plain = serving.Engine(params=_params(), config=CFG, num_slots=3,
                           max_seq_len=96, page_size=8, prefill_chunk=8)
    req = serving.Request(np.arange(3, 9), max_new_tokens=4)
    plain.submit(req)
    plain.step()
    state = plain.state_dict()
    # simulate a snapshot written before the adapter subsystem existed
    state["meta"].pop("adapters", None)
    state.pop("aid", None)
    plain2 = serving.Engine(params=_params(), config=CFG, num_slots=3,
                            max_seq_len=96, page_size=8, prefill_chunk=8)
    plain2.load_state_dict(state)
    res = plain2.run()
    assert res[req.request_id].tokens == _ref_tokens(req.prompt, 4)


# ---------------------------------------------------------------------------
# tensor-parallel: mixed-adapter batches bitwise at mp in {2, 4}


@pytest.mark.parametrize("mp", [2, 4])
def test_mp_mixed_adapter_bitwise_vs_single_chip(mp, devices8):
    """Deltas shard with the output channels (B slabs column-sharded,
    compose-before-gather): the mp engine's mixed-adapter streams are
    bitwise the single-chip references."""
    from paddle_tpu.distributed import env as dist_env
    try:
        eng = _load_std(_engine(mp=mp, num_slots=3))
        rng = np.random.default_rng(23)
        reqs = _mixed_requests((1, 0, 2, 1), rng)
        results = eng.run(reqs)
        # reference runs SINGLE-CHIP on host copies of the same slab
        # content (device_get is a gather — exact)
        slabs = {k: (np.asarray(jax.device_get(a)),
                     np.asarray(jax.device_get(b)))
                 for k, (a, b) in eng.adapters.device_slabs().items()}
        for r in reqs:
            aid = r.adapter or 0
            ref = _ref_tokens(r.prompt, r.max_new_tokens,
                              adapters=(aid, slabs))
            got = results[r.request_id].tokens
            assert got == ref[:len(got)] and got, \
                f"mp={mp} adapter {aid}: {got} != {ref}"
    finally:
        paddle.set_flags({"FLAGS_comm_backend": "", "FLAGS_serving_mp": 0})
        dist_env.set_mesh(None)


# ---------------------------------------------------------------------------
# supervisor: fleet-level ops, respawn and rolling restart carry the set


def _factory():
    return _engine(num_slots=3)


def test_supervisor_fleet_adapter_ops_survive_replica_kill(tmp_path):
    """sup.load_adapter applies fleet-wide and rides the live set: a
    replica killed mid-decode respawns SERVING the adapters; every
    mixed-adapter request completes bitwise with zero drops."""
    profiler.reset_serving_counters()
    sup = serving.ServingSupervisor(_factory, num_replicas=2,
                                    snapshot_dir=tmp_path, snapshot_every=2)
    sup.load_adapter(1, _delta(1), alpha=8.0)
    sup.load_adapter(2, _delta(2), alpha=8.0)
    rng = np.random.default_rng(29)
    reqs = _mixed_requests((1, 2, 0, 1, 2, 1), rng)
    with fi.inject(fi.FaultPlan(kill_at_decode_step=3,
                                kill_engine_tag="replica0")):
        results = sup.run(reqs)
        assert fi.stats()["serving_kills"] == 1
    c = smetrics.serving_counters()
    assert c["dropped"] == 0 and c["respawns"] >= 1
    # fleet-level ops count once, not per replica
    assert c["adapter_loads"] == 2
    eng = next(r.engine for r in sup._replicas if r.engine is not None)
    assert sorted(eng.adapters.resident_ids()) == [1, 2]
    _check_bitwise(eng, reqs, results)
    assert sup.telemetry()["adapters_live"] == 2


def test_supervisor_rolling_restart_and_evict_swap():
    sup = serving.ServingSupervisor(_factory, num_replicas=2)
    sup.load_adapter(1, _delta(1), alpha=8.0)
    sup.load_adapter(2, _delta(2), alpha=8.0)
    sup.rolling_restart()
    for rep in sup._replicas:
        assert sorted(rep.engine.adapters.resident_ids()) == [1, 2]
    sup.swap_adapter(1, _delta(51), alpha=8.0)
    sup.evict_adapter(2)
    for rep in sup._replicas:
        assert rep.engine.adapters.resident_ids() == (1,)
    # a rolling restart AFTER the evict must not resurrect adapter 2
    sup.rolling_restart()
    for rep in sup._replicas:
        assert rep.engine.adapters.resident_ids() == (1,)
    req = serving.Request(np.arange(5, 11), max_new_tokens=5, adapter=1)
    results = sup.run([req])
    eng = sup._replicas[0].engine
    _check_bitwise(eng, [req], results)


# ---------------------------------------------------------------------------
# observability: gauges, token shares, summary segment, export round-trip


def test_adapter_metrics_and_summary_segment():
    profiler.reset_serving_counters()
    eng = _load_std(_engine())
    rng = np.random.default_rng(31)
    eng.run(_mixed_requests((1, 2, 0, 1), rng))
    c = smetrics.serving_counters()
    assert c["adapters_resident"] == 2
    assert c["adapter_delta_bytes"] == eng.adapters.delta_bytes() > 0
    assert c["adapter_tokens_1"] > 0 and c["adapter_tokens_2"] > 0
    shares = [v for k, v in c.items()
              if k.startswith("adapter_token_share_")]
    assert abs(sum(shares) - 1.0) < 1e-9
    summary = smetrics.serving_summary()
    assert "adapters: 2/3 resident" in summary
    assert "tok-share" in summary
    # export/import carries the per-adapter tallies (snapshot metrics)
    state = smetrics.export_state()
    profiler.reset_serving_counters()
    assert "adapter_tokens_1" not in smetrics.serving_counters()
    smetrics.import_state(state)
    assert smetrics.serving_counters()["adapter_tokens_1"] \
        == c["adapter_tokens_1"]


def test_request_trace_carries_adapter_span():
    eng = _load_std(_engine(trace=True))
    req = serving.Request(np.arange(2, 8), max_new_tokens=3, adapter=1)
    eng.run([req])
    ad = [e for e in req.trace.spans if e["name"] == "adapter"]
    assert ad and ad[0]["adapter_id"] == 1


def test_registry_hbm_accounting_and_state_roundtrip():
    spec = AdapterSpec(slots=4, rank=8)
    reg = AdapterRegistry(CFG, spec)
    assert reg.delta_bytes() == 0
    reg.load(2, _delta(2, rank=8), alpha=16.0)
    assert reg.delta_bytes() == reg.row_bytes() > 0
    assert reg.slab_bytes() >= (spec.slots + 1) * reg.row_bytes()
    state = reg.state_dict()
    reg2 = AdapterRegistry(CFG, spec)
    reg2.load_state_dict(state)
    assert reg2.resident_ids() == (2,)
    for name in ("out_w", "up_w", "down_w"):
        a1, b1 = reg._host[name]
        a2, b2 = reg2._host[name]
        np.testing.assert_array_equal(a1, a2)
        np.testing.assert_array_equal(b1, b2)


# ---------------------------------------------------------------------------
# smoke rung (tools_serving_smoke --adapters)


def _load_smoke():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "tools_serving_smoke",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools_serving_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_adapter_deterministic_subrung():
    """tools_serving_smoke's many-model rung in deterministic tiny mode:
    mixed-adapter parity vs solo references, frozen executables across
    hot adapter ops, and the HBM ledger — no wall-clock gates."""
    mod = _load_smoke()
    out = mod.run_adapter_rung(deterministic=True)
    assert out["parity"]
    assert out["trace_frozen"]
    assert out["hbm"]["adapter_slab_bytes"] > 0
    # N low-rank variants must cost a small fraction of N weight copies
    assert out["hbm"]["ratio"] < 0.5
    assert out["adapter_ops"]["swaps"] >= 1 and out["adapter_ops"]["evicts"] >= 1


@pytest.mark.slow
def test_smoke_adapter_beats_swap_per_tenant():
    mod = _load_smoke()
    out = mod.run_adapter_rung(quick=True)
    assert out["speedup"] >= 1.15
    assert out["hbm"]["ratio"] < 0.5
