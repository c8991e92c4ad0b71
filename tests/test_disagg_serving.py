"""Disaggregated prefill/decode serving (serving/kv_transfer.py +
engine roles + role/cache-aware supervisor routing).

Gates (the PR acceptance criteria):
  * BITWISE parity: a 1-prefill + N-decode fleet produces token streams
    identical to a single engine for any admission order, greedy AND
    sampled, and per dtype config (int8/fp8 wires carry per-page
    scales) — the handoff seat is the exact-prefix-hit path;
  * cross-engine page-table splice invariants: after a transfer both
    pools conserve pages, account every refcount, staged pages are
    ledgered mid-install and gone after the seat, and CoW divergence on
    transferred pages stays independent;
  * per-role trace discipline: a prefill worker NEVER runs the [B,1]
    decode dispatch, a decode worker's chunk rungs collapse to the
    page-sized seat re-forward, and the global paged_traces counter is
    frozen once a disaggregated fleet has warmed;
  * every transfer appears as a "transfer" span that reconciles with
    the request's TTFT;
  * chaos: killing the decode worker mid-stream re-offers the retained
    payloads, killing the prefill worker replays — zero drops, parity
    both ways; losing ALL decode capacity rebalances a prefill worker's
    role; losing all prefill capacity falls back to pure-decode;
  * satellites: ``Engine.prefix_page_hashes`` is a stable routing key,
    the supervisor load probe folds the in-flight prefill backlog, and
    prefix-cache counters seed across
    ``load_state_dict(restore_metrics=False)`` without clobbering a
    warm ledger.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.gpt_hybrid import init_gpt_params
from paddle_tpu.observability import tracing
from paddle_tpu.serving import metrics
from paddle_tpu.serving import supervisor as sup_mod
from paddle_tpu.serving.supervisor import ServingSupervisor
from paddle_tpu.utils import fault_injection as fi

CFG = GPTConfig(vocab_size=96, hidden_size=64, num_layers=2, num_heads=4,
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
    kw.setdefault("prefill_chunk", 8)   # == page_size: decode-side rungs
    return serving.Engine(params=_params(), config=CFG, **kw)


# every prompt LONGER than page_size rides the full prefill->transfer->
# seat pipeline; the two short ones exercise the direct-to-decode path
_SHAPES = ((13, 4), (21, 5), (9, 6), (17, 4), (3, 5), (33, 4))


def _mixed_requests(n, rng, **kw):
    reqs = []
    for i in range(n):
        plen, mnt = _SHAPES[i % len(_SHAPES)]
        kw.setdefault("seed", None)
        reqs.append(serving.Request(rng.integers(0, CFG.vocab_size, plen),
                                    max_new_tokens=mnt,
                                    **{**kw, "seed": i}))
    return reqs


def _tok_lists(results, reqs):
    return [results[r.request_id].tokens for r in reqs]


def _golden(seed, n=6, **kw):
    """Single-engine reference streams for the same traffic shape."""
    reqs = _mixed_requests(n, np.random.default_rng(seed), **kw)
    out = _tok_lists(_engine(num_slots=4, max_queue=16).run(reqs), reqs)
    return reqs, out


def _fleet(roles, factory=None, **sup_kw):
    return ServingSupervisor(factory or (lambda: _engine()),
                             num_replicas=len(roles), roles=roles, **sup_kw)


# ---------------------------------------------------------------------------
# construction / validation


def test_roles_validation_errors():
    with pytest.raises(ValueError, match="2 entries for 3"):
        ServingSupervisor(lambda: _engine(), num_replicas=3,
                          roles=("prefill", "decode"))
    with pytest.raises(ValueError, match="chef"):
        ServingSupervisor(lambda: _engine(), num_replicas=2,
                          roles=("prefill", "chef"))
    with pytest.raises(ValueError, match="decode-"):
        ServingSupervisor(lambda: _engine(), num_replicas=2,
                          roles=("prefill", "prefill"))
    eng = _engine()
    with pytest.raises(ValueError, match="role"):
        eng.set_role("chef")
    # a non-idle engine refuses the flip (mid-stream strand)
    busy = _engine()
    busy.submit(serving.Request([1, 2, 3], max_new_tokens=2))
    with pytest.raises(RuntimeError, match="idle|drain"):
        busy.set_role("prefill")


# ---------------------------------------------------------------------------
# satellite: stable routing key


def test_prefix_page_hashes_stable_routing_key():
    """(page_hashes, exact) is engine-independent, one hash per FULL
    page of cumulative prefix, shared prefixes share leading hashes and
    diverge exactly at the diverging page."""
    e1, e2 = _engine(), _engine(num_slots=4)
    p = list(range(1, 22))                       # 21 tokens, ps=8
    h1, x1 = e1.prefix_page_hashes(p)
    h2, x2 = e2.prefix_page_hashes(np.asarray(p))
    assert (h1, x1) == (h2, x2)
    assert len(h1) == len(p) // e1.page_size == 2
    q = p[:16] + [77, 78, 79, 80, 81]            # same first 2 pages
    hq, xq = e1.prefix_page_hashes(q)
    assert hq[:2] == h1[:2] and xq != x1
    r = p[:8] + [50] + p[9:]                     # page 2 diverges
    hr, _ = e1.prefix_page_hashes(r)
    assert hr[0] == h1[0] and hr[1] != h1[1]
    # sub-page prompts: no full page, exact key only
    hs, xs = e1.prefix_page_hashes([1, 2, 3])
    assert hs == () and xs


# ---------------------------------------------------------------------------
# the tentpole parity contract


def test_disagg_bitwise_parity_greedy_and_order_invariant():
    """1 prefill + 1 decode == single engine, bitwise, for two admission
    orders."""
    base_reqs, base = _golden(31)
    golden = dict(zip((r.request_id for r in base_reqs), base))

    for order in (lambda rs: rs, lambda rs: list(reversed(rs))):
        reqs = _mixed_requests(6, np.random.default_rng(31))
        id_map = dict(zip((r.request_id for r in reqs),
                          (r.request_id for r in base_reqs)))
        sup = _fleet(("prefill", "decode"))
        results = sup.run(order(reqs))
        sup.shutdown()
        assert len(results) == len(reqs)
        for r in reqs:
            assert results[r.request_id].tokens == \
                golden[id_map[r.request_id]], r.request_id
    c = metrics.serving_counters()
    assert c["prefill_handoffs"] >= 8 and c["transfers"] >= 8
    assert c["transfer_pages"] > 0 and c["transfer_bytes"] > 0
    assert c["dropped"] == 0


def test_disagg_bitwise_parity_sampled():
    """Sampled streams (per-request seeds): the handoff seat re-splits
    the request's own threefry key exactly like the single engine's
    exact-prefix-hit path — streams stay bitwise."""
    kw = dict(do_sample=True, temperature=0.8, top_p=0.9)
    base_reqs, base = _golden(32, **kw)
    golden = dict(zip((r.request_id for r in base_reqs), base))
    reqs = _mixed_requests(6, np.random.default_rng(32), **kw)
    id_map = dict(zip((r.request_id for r in reqs),
                      (r.request_id for r in base_reqs)))
    sup = _fleet(("prefill", "decode", "decode"))
    results = sup.run(reqs)
    sup.shutdown()
    for r in reqs:
        assert results[r.request_id].tokens == golden[id_map[r.request_id]]


@pytest.mark.parametrize("dtype", ["int8", "fp8"])
def test_disagg_quantized_parity_scales_ride_the_wire(dtype):
    """int8/fp8 KV pools transfer at the storage dtype with per-page
    scales in the payload: the disaggregated stream equals the
    single-engine QUANTIZED stream at that config."""
    rng = np.random.default_rng(33)
    base_reqs = _mixed_requests(5, rng)
    base = _tok_lists(_engine(num_slots=4, max_queue=16,
                              quant=dtype).run(base_reqs), base_reqs)
    golden = dict(zip((r.request_id for r in base_reqs), base))

    before = metrics.serving_counters()["transfer_bytes"]
    reqs = _mixed_requests(5, np.random.default_rng(33))
    id_map = dict(zip((r.request_id for r in reqs),
                      (r.request_id for r in base_reqs)))
    sup = _fleet(("prefill", "decode"),
                 factory=lambda: _engine(quant=dtype))
    results = sup.run(reqs)
    sup.shutdown()
    for r in reqs:
        assert results[r.request_id].tokens == golden[id_map[r.request_id]]
    # quantized pages are 1-byte elements + fp32 scale sidecars: the
    # byte counter moved, and by less than an fp32 wire would
    assert metrics.serving_counters()["transfer_bytes"] > before


# ---------------------------------------------------------------------------
# cross-engine page-table splice invariants (manual two-engine harness)


def _pump_handoff(src):
    """Drive a prefill worker until its (single) outbound transfer is
    complete; returns the finished KVTransfer."""
    tr = None
    for _ in range(64):
        src.step()
        tr = tr or next(iter(src.take_outbound()), None)
        if tr is not None and tr.done:
            return tr
    raise AssertionError("handoff never completed")


@pytest.mark.parametrize("quant", [None, "int8", "fp8"])
def test_splice_invariants_and_scale_transport(quant):
    """The raw engine-to-engine splice: payloads carry scales exactly
    when the pool is quantized, staged pages are ledgered during the
    install, and after the seat both pools conserve + account."""
    paddle.set_flags({"FLAGS_serving_transfer_pages_per_boundary": 1})
    try:
        src = _engine(quant=quant).set_role("prefill")
        dst = _engine(quant=quant, num_slots=4)
        prompt = list(range(1, 22))                      # 3 pages at ps=8
        req = serving.Request(prompt, max_new_tokens=4, seed=5)
        src.submit(req)
        tr = _pump_handoff(src)
        assert tr.total_pages == 3 and len(tr.pages) == 3
        assert src.active_slots == 0                     # slot freed at handoff
        for p in tr.pages:
            if quant is None:
                assert p.k_scale is None and p.v_scale is None
            else:
                assert p.k_scale is not None and p.v_scale is not None
                # one scale per layer for this physical page
                assert p.k_scale.shape == (CFG.num_layers,)
            assert p.nbytes > 0
        sbal = src.pool.balance()
        assert sbal["conserved"] and sbal["refcounts_accounted"]

        dst.offer_transfer(tr)
        dst.step()                                       # budget=1: partial
        assert len(dst.pool.staged_pages(req.request_id)) == 1
        mid = dst.pool.balance()                         # staged pages ledger
        assert mid["conserved"] and mid["refcounts_accounted"]
        results = dst.run()
        assert req.request_id in results
        assert not dst.pool.staged_pages(req.request_id)
        dbal = dst.pool.balance()
        assert dbal["conserved"] and dbal["refcounts_accounted"]

        # the transferred stream equals a plain single-engine run
        solo = _engine(quant=quant).run(
            [serving.Request(prompt, max_new_tokens=4, seed=5)])
        assert results[req.request_id].tokens == \
            list(solo.values())[0].tokens
    finally:
        paddle.set_flags({"FLAGS_serving_transfer_pages_per_boundary": 4})


def test_splice_cow_divergence_stays_independent():
    """A sibling that prefix-hits TRANSFERRED pages diverges through the
    normal CoW path: both streams match unshared baselines and the pool
    still balances."""
    src = _engine().set_role("prefill")
    dst = _engine(num_slots=4)
    base = list(range(1, 17))                            # 2 full pages
    req = serving.Request(base + [20, 21, 22], max_new_tokens=4, seed=1)
    src.submit(req)
    dst.offer_transfer(_pump_handoff(src))
    out1 = dst.run()
    # sibling shares the 2 transferred full pages, diverges after
    sib = serving.Request(base + [30, 31, 32], max_new_tokens=4, seed=2)
    hits0 = metrics.serving_counters()["prefix_hits"]
    out2 = dst.run([sib])
    assert metrics.serving_counters()["prefix_hits"] > hits0
    solo = _engine(prefix_cache=False)
    s1 = solo.run([serving.Request(base + [20, 21, 22],
                                   max_new_tokens=4, seed=1)])
    s2 = solo.run([serving.Request(base + [30, 31, 32],
                                   max_new_tokens=4, seed=2)])
    assert list(out1.values())[0].tokens == list(s1.values())[0].tokens
    assert out2[sib.request_id].tokens == list(s2.values())[0].tokens
    bal = dst.pool.balance()
    assert bal["conserved"] and bal["refcounts_accounted"]


def test_transfer_geometry_mismatch_refused():
    src = _engine().set_role("prefill")
    req = serving.Request(list(range(1, 14)), max_new_tokens=3)
    src.submit(req)
    tr = _pump_handoff(src)
    with pytest.raises(ValueError, match="page_size"):
        _engine(page_size=16, prefill_chunk=16).offer_transfer(tr)
    with pytest.raises(ValueError, match="kv_dtype"):
        _engine(quant="int8").offer_transfer(tr)
    with pytest.raises(ValueError, match="prefill worker"):
        _engine().set_role("prefill").offer_transfer(tr)


# ---------------------------------------------------------------------------
# per-role trace discipline


def test_per_role_dispatch_gates_and_frozen_traces():
    """Prefill workers never hit the [B,1] decode dispatch; decode
    workers' chunk rungs collapse to the page-sized seat re-forward;
    and a SECOND identical fleet adds zero paged traces."""
    sup = _fleet(("prefill", "decode"))
    reqs = _mixed_requests(6, np.random.default_rng(34))
    sup.run(reqs)
    pre = sup._replicas[0].engine
    dec = sup._replicas[1].engine
    assert pre.role == "prefill" and dec.role == "decode"
    assert pre._decode_dispatches == 0
    assert pre._chunk_rungs                       # it DID prefill
    assert dec._decode_dispatches > 0
    assert dec._chunk_rungs <= {dec.page_size}    # seat re-forward only...
    sup.shutdown()
    warm = metrics.serving_counters()["paged_traces"]
    sup2 = _fleet(("prefill", "decode"))
    sup2.run(_mixed_requests(6, np.random.default_rng(35), do_sample=True,
                             temperature=0.9))
    sup2.shutdown()
    assert metrics.serving_counters()["paged_traces"] == warm


def test_transfer_span_reconciles_with_ttft():
    """Every transferred request's trace carries exactly one "transfer"
    span (bytes/pages/dtype/src meta) inside [submit, first_token]."""
    sup = _fleet(("prefill", "decode"),
                 factory=lambda: _engine(trace=True))
    req = serving.Request(list(range(1, 22)), max_new_tokens=4, seed=3)
    results = sup.run([req])
    sup.shutdown()
    assert req.request_id in results
    spans = [s for s in req.trace.spans if s["name"] == "transfer"]
    assert len(spans) == 1
    sp = spans[0]
    assert sp["pages"] == 3 and sp["bytes"] > 0
    assert sp["src"] and sp["dtype"]
    assert req.submit_t <= sp["t0"] <= sp["t1"]
    assert sp["t1"] <= req.first_token_t          # TTFT covers the wire
    assert any(s["name"] == "handoff" for s in req.trace.spans)


# ---------------------------------------------------------------------------
# routing: affinity / short prompts / fallback


def test_affinity_repeat_prefix_skips_transfer():
    """A second wave sharing a cached long prefix routes straight to the
    decode worker that holds it: affinity_hits bumps, NO new transfer."""
    sup = _fleet(("prefill", "decode"))
    prompt = np.random.default_rng(36).integers(0, CFG.vocab_size, 21)
    w1 = serving.Request(prompt, max_new_tokens=5, seed=4)
    r1 = sup.run([w1])
    c1 = metrics.serving_counters()
    t1, a1 = c1["transfers"], c1["affinity_hits"]
    assert sup._replicas[1].engine.prefix_coverage(prompt) >= 16
    w2 = serving.Request(prompt, max_new_tokens=5, seed=4)
    r2 = sup.run([w2])
    sup.shutdown()
    c2 = metrics.serving_counters()
    assert c2["affinity_hits"] == a1 + 1
    assert c2["transfers"] == t1                  # transfer SKIPPED
    assert r1[w1.request_id].tokens == r2[w2.request_id].tokens


def test_short_prompts_route_direct_no_handoff():
    """Sub-page prompts skip the pipeline (a one-page handoff costs more
    than the chunk it saves) without counting as affinity hits."""
    c0 = metrics.serving_counters()
    sup = _fleet(("prefill", "decode"))
    reqs = [serving.Request([i + 1, i + 2, i + 3], max_new_tokens=3,
                            seed=i) for i in range(3)]
    base_reqs = [serving.Request([i + 1, i + 2, i + 3], max_new_tokens=3,
                                 seed=i) for i in range(3)]
    base = _tok_lists(_engine().run(base_reqs), base_reqs)
    results = sup.run(reqs)
    sup.shutdown()
    c = metrics.serving_counters()
    assert c["prefill_handoffs"] == c0["prefill_handoffs"]
    assert c["affinity_hits"] == c0["affinity_hits"]
    assert _tok_lists(results, reqs) == base


def test_pure_decode_fallback_when_prefill_capacity_dies():
    """The prefill worker dies past max_restarts: traffic falls back to
    pure-decode (counted) and still completes with parity."""
    base_reqs, base = _golden(37, n=4)
    golden = dict(zip((r.request_id for r in base_reqs), base))
    reqs = _mixed_requests(4, np.random.default_rng(37))
    id_map = dict(zip((r.request_id for r in reqs),
                      (r.request_id for r in base_reqs)))
    sup = _fleet(("prefill", "decode", "decode"), max_restarts=0)
    with fi.inject(fi.FaultPlan(kill_at_decode_step=1,
                                kill_engine_tag="replica0")):
        results = sup.run(reqs)
        assert fi.stats()["serving_kills"] == 1
    # second wave: no prefill capacity exists at ALL -> counted fallback
    fb0 = metrics.serving_counters()["disagg_fallbacks"]
    reqs2 = _mixed_requests(2, np.random.default_rng(38))
    results2 = sup.run(reqs2)
    sup.shutdown()
    assert len(results) == len(reqs) and len(results2) == len(reqs2)
    for r in reqs:
        assert results[r.request_id].tokens == golden[id_map[r.request_id]]
    assert metrics.serving_counters()["disagg_fallbacks"] > fb0
    assert metrics.serving_counters()["dropped"] == 0


# ---------------------------------------------------------------------------
# chaos: kills mid-stream, zero drops, parity


def test_kill_decode_worker_mid_stream_zero_drops(tmp_path):
    """The decode worker dies while transfers are in flight: retained
    payloads re-offer to the respawned worker (or re-route), nothing is
    recomputed from scratch unless the source died too — zero drops,
    bitwise parity."""
    base_reqs, base = _golden(39)
    golden = dict(zip((r.request_id for r in base_reqs), base))
    reqs = _mixed_requests(6, np.random.default_rng(39))
    id_map = dict(zip((r.request_id for r in reqs),
                      (r.request_id for r in base_reqs)))
    sup = _fleet(("prefill", "decode"), snapshot_dir=str(tmp_path),
                 snapshot_every=2)
    with fi.inject(fi.FaultPlan(kill_at_decode_step=3,
                                kill_engine_tag="replica1")):
        results = sup.run(reqs)
        assert fi.stats()["serving_kills"] == 1
    sup.shutdown()
    assert len(results) == len(reqs)
    for r in reqs:
        assert results[r.request_id].tokens == golden[id_map[r.request_id]]
    c = metrics.serving_counters()
    assert c["dropped"] == 0 and c["respawns"] >= 1


def test_kill_prefill_worker_mid_stream_zero_drops():
    """The prefill worker dies abruptly (payload source gone): its
    un-handed-off requests replay — zero drops, parity (sampled too)."""
    kw = dict(do_sample=True, temperature=0.7, top_p=0.95)
    base_reqs, base = _golden(40, **kw)
    golden = dict(zip((r.request_id for r in base_reqs), base))
    reqs = _mixed_requests(6, np.random.default_rng(40), **kw)
    id_map = dict(zip((r.request_id for r in reqs),
                      (r.request_id for r in base_reqs)))
    sup = _fleet(("prefill", "decode"))
    with fi.inject(fi.FaultPlan(kill_at_decode_step=2,
                                kill_engine_tag="replica0")):
        results = sup.run(reqs)
        assert fi.stats()["serving_kills"] == 1
    sup.shutdown()
    assert len(results) == len(reqs)
    for r in reqs:
        assert results[r.request_id].tokens == golden[id_map[r.request_id]]
    assert metrics.serving_counters()["dropped"] == 0


def test_role_rebalance_covers_lost_decode_capacity():
    """The ONLY decode worker dies past max_restarts: the supervisor
    flips the least-loaded prefill worker to decode (counted, gauged)
    and every request still completes with parity."""
    base_reqs, base = _golden(41, n=4)
    golden = dict(zip((r.request_id for r in base_reqs), base))
    reqs = _mixed_requests(4, np.random.default_rng(41))
    id_map = dict(zip((r.request_id for r in reqs),
                      (r.request_id for r in base_reqs)))
    sup = _fleet(("prefill", "decode"), max_restarts=0)
    with fi.inject(fi.FaultPlan(kill_at_decode_step=2,
                                kill_engine_tag="replica1")):
        results = sup.run(reqs)
        assert fi.stats()["serving_kills"] == 1
    assert len(results) == len(reqs)
    for r in reqs:
        assert results[r.request_id].tokens == golden[id_map[r.request_id]]
    c = metrics.serving_counters()
    assert c["role_rebalances"] >= 1 and c["dropped"] == 0
    rep0 = sup._replicas[0]
    assert rep0.role == "decode" and rep0.configured_role == "prefill"
    tel = sup.telemetry()
    assert tel["replica0"]["role"] == "decode"
    sup.shutdown()


# ---------------------------------------------------------------------------
# satellite: load probe folds the prefill backlog


def test_load_probe_folds_prefill_backlog():
    eng = _engine(num_slots=2)
    giant = serving.Request(list(range(1, 65)), max_new_tokens=2, seed=0)
    queued = serving.Request(list(range(1, 25)), max_new_tokens=2, seed=1)
    eng.submit(giant)
    eng.submit(serving.Request([1, 2, 3], max_new_tokens=2, seed=2))
    eng.submit(queued)                       # 2 slots -> stays queued
    eng.step()                               # one 8-token chunk each
    backlog = eng.prefill_backlog()
    assert backlog >= (64 - 8) + 24          # mid-prefill remainder + queue
    rep = sup_mod._Replica(0, None, None)
    rep.engine, rep.state = eng, "up"
    # the probe exceeds the naive queue+slots load by backlog/chunk
    naive = eng.queue_depth + eng.active_slots
    assert rep.load == naive + backlog / eng.prefill_chunk
    eng.run()                                # drain: backlog collapses
    assert eng.prefill_backlog() == 0
    assert rep.load == 0


# ---------------------------------------------------------------------------
# satellite: prefix-counter lifecycle across restore_metrics=False


def test_prefix_counters_seed_across_restore(tmp_path):
    """A respawned engine restoring a snapshot with live cache entries
    seeds the prefix counter family from the snapshot — hit-rate
    reporting matches the entries that came back; a WARM ledger is never
    clobbered."""
    base = list(range(1, 17))
    eng = _engine()
    eng.run([serving.Request(base + [20], max_new_tokens=3, seed=1)])
    eng.run([serving.Request(base + [30], max_new_tokens=3, seed=2)])
    snap = eng.state_dict()
    snap_prefix = {k: snap["metrics"]["counters"][k]
                   for k in ("prefix_lookups", "prefix_hits",
                             "prefix_tokens_reused")}
    assert snap_prefix["prefix_hits"] >= 1

    metrics.reset_serving_counters()         # cold respawn: zero ledger
    fresh = _engine()
    fresh.load_state_dict(snap)              # restore_metrics=False
    assert fresh.pool.cache_entries > 0
    c = metrics.serving_counters()
    assert {k: c[k] for k in snap_prefix} == snap_prefix

    # warm ledger: a second restore must NOT clobber live counts
    metrics.bump("prefix_lookups")
    live = metrics.serving_counters()["prefix_lookups"]
    _engine().load_state_dict(snap)
    assert metrics.serving_counters()["prefix_lookups"] == live
    assert not metrics.seed_prefix_counters(snap["metrics"]["counters"])


# ---------------------------------------------------------------------------
# smoke sub-rung (fast deterministic; throughput/p99 gates are slow)


def _load_smoke():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tools_serving_smoke", "tools_serving_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_disagg_deterministic_subrung():
    """tools_serving_smoke --disagg in deterministic tiny mode: bitwise
    parity vs the single engine, every long prompt handed off, transfer
    bytes ledgered by dtype, affinity hits on the repeat wave — no
    wall-clock gates (slow rung below)."""
    mod = _load_smoke()
    out = mod.run_disagg_rung(quick=True, deterministic=True)
    assert out["parity"]
    assert out["prefill_handoffs"] > 0 and out["transfers"] > 0
    assert out["transfer_bytes"] > 0
    assert out["transfer_dtype"]
    assert out["affinity_hits"] > 0 and out["affinity_hit_rate"] > 0
    assert out["dropped"] == 0


@pytest.mark.slow
def test_smoke_disagg_throughput_gate():
    """Full rung under mixed traffic: disaggregation takes prefill off
    the token path — the decode worker's boundary p99 (what a user's
    next token waits behind once workers run on their own chips) beats
    the colocated fleet's, whose boundaries carry whole XL chunk rungs.
    Wall tokens/s is reported (this driver steps replicas serially, so
    fleet wall time sums both workers) and must not collapse."""
    mod = _load_smoke()
    out = mod.run_disagg_rung(quick=True, deterministic=False)
    assert out["parity"] and out["dropped"] == 0
    assert out["disagg"]["decode_boundary_p99"] <= \
        out["colocated"]["decode_boundary_p99"]
    assert out["disagg"]["tokens_per_s"] >= \
        0.5 * out["colocated"]["tokens_per_s"]
