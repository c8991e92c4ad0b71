"""Speculative multi-token decoding inside the static-executable serving
discipline (serving speculate_k): a k-token self-draft pass plus ONE fused
[B, k+1] verify dispatch per boundary, rejected KV rewound byte-for-byte.

Gates:
  * greedy speculative streams are BITWISE the plain engine's for any
    admission order, and sampled streams replay generate_from_params
    exactly (the verify key splits once per EMITTED token only);
  * KV-rewind invariant: after running mixed traffic with real rejections
    the paged pool (minus the trash page), the page table and the
    allocator balance are byte-identical to a plain engine that decoded
    the same tokens one at a time;
  * static executables: one draft + one verify trace per config, FROZEN
    under slot churn, admission reordering and accept/reject mixes; a
    plain engine's trace counters never move when a spec engine runs;
  * Request(speculate=) opt-out and validation; engine composition gates
    (paged-only, single-chip);
  * spec state rides the snapshot: state_dict()["spec"] carries the
    draft config + params version and a mid-traffic restore is bitwise;
  * observability: accept_rate / tokens_per_dispatch derived counters and
    per-request "speculate" spans reconcile with the emitted-token ledger;
  * the tools_serving_smoke --spec rung: deterministic sub-rung in tier-1,
    timed >= 1.3x throughput gate slow-marked.
"""
import importlib.util
import os

import numpy as np
import pytest
import jax

from paddle_tpu import profiler, serving
from paddle_tpu.models.generation import generate_from_params
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.gpt_hybrid import init_gpt_params
from paddle_tpu.observability import tracing
from paddle_tpu.serving.quant import QuantSpec

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
    # num_slots=7 is UNIQUE across the suite: executables are shared per
    # shape process-wide, so borrowing another file's batch shape would
    # make trace-count gates order-dependent
    kw.setdefault("num_slots", 7)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return serving.Engine(params=_params(), config=CFG, **kw)


def _spec_engine(**kw):
    kw.setdefault("speculate_k", 4)
    return _engine(**kw)


def _ref_tokens(prompt, max_new, **kw):
    out = np.asarray(generate_from_params(_params(), np.asarray(prompt)[None],
                                          CFG, max_new_tokens=max_new,
                                          **kw)._data)
    return out[0, len(prompt):].tolist()


_SHAPES = ((3, 5), (5, 7), (9, 4), (13, 8), (21, 6), (37, 5))


def _mixed_requests(n, rng, sample_every=3, **kw):
    """n requests over the shape palette; every ``sample_every``-th is
    sampled with its own temperature/top_p/seed (sampled slots REJECT
    draft tokens far more often — the rewind path's real workout)."""
    reqs = []
    for i in range(n):
        plen, mnt = _SHAPES[i % len(_SHAPES)]
        rkw = dict(kw)
        if sample_every and i % sample_every == 1:
            rkw.update(do_sample=True, temperature=0.7 + 0.1 * (i % 4),
                       top_p=0.85, seed=11 + i)
        reqs.append(serving.Request(rng.integers(0, CFG.vocab_size, plen),
                                    max_new_tokens=mnt, **rkw))
    return reqs


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
# bitwise parity gates


def test_greedy_parity_any_admission_order():
    """Greedy speculative output is bitwise the non-speculative engine's
    for ANY admission order: all-at-once, reversed, and trickled one
    request per boundary."""
    for plan in ("all_at_once", "reversed", "trickled"):
        eng = _spec_engine()
        fresh = _mixed_requests(8, np.random.default_rng(0), sample_every=0)
        golden = {r.request_id: _ref_tokens(r.prompt, r.max_new_tokens)
                  for r in fresh}
        if plan == "trickled":
            pending = list(fresh)
            res = {}
            while pending or eng.queue_depth or eng.active_slots:
                if pending:
                    eng.submit(pending.pop(0))
                eng.step()
                res.update(eng.pop_results())
        elif plan == "reversed":
            res = eng.run(list(reversed(fresh)))
        else:
            res = eng.run(fresh)
        for r in fresh:
            assert res[r.request_id].tokens == golden[r.request_id], \
                f"admission order {plan}: {r.request_id} diverged"


def test_sampled_stream_replays_generate():
    """Sampled speculative streams replay generate_from_params EXACTLY:
    the verify scan splits the slot key once per emitted token, so the
    threefry stream is position-for-position the sequential one."""
    eng = _spec_engine()
    prompt = np.array([5, 17, 33, 2, 9])
    req = serving.Request(prompt, max_new_tokens=8, do_sample=True,
                          temperature=0.8, top_p=0.9, seed=7)
    res = eng.run([req])[req.request_id]
    assert res.tokens == _ref_tokens(prompt, 8, do_sample=True,
                                     temperature=0.8, top_p=0.9, seed=7)
    # no nucleus cut
    req2 = serving.Request(np.arange(3, 11), max_new_tokens=8,
                           do_sample=True, temperature=1.3, seed=11)
    res = eng.run([req2])[req2.request_id]
    assert res.tokens == _ref_tokens(np.arange(3, 11), 8, do_sample=True,
                                     temperature=1.3, seed=11)


def test_mixed_greedy_sampled_batch_parity():
    """Greedy and sampled slots share the one fused verify executable
    (per-slot sampling params are traced operands) and every stream stays
    bitwise its single-request reference."""
    eng = _spec_engine()
    reqs = _mixed_requests(9, np.random.default_rng(1))
    golden = _golden(reqs)
    results = eng.run(reqs)
    for r in reqs:
        assert results[r.request_id].tokens == golden[r.request_id]


def test_draft_sources_parity():
    """Both draft rungs — int8 self-draft and the shallow-layer draft —
    and the quantized-engine compose (degenerate self-draft) keep the
    output contract: the draft only ever PROPOSES; the served weights
    decide."""
    reqs0 = _mixed_requests(6, np.random.default_rng(2))
    golden = _golden(reqs0)
    for kw in ({"draft_source": "quant"},
               {"draft_source": "shallow"},
               {"draft_source": "shallow", "draft_layers": 1},
               {"draft_source": "quant", "quant": QuantSpec("int8", "int8")}):
        quant = kw.pop("quant", None)
        eng = _spec_engine(quant=quant, **kw)
        reqs = _mixed_requests(6, np.random.default_rng(2))
        results = eng.run(reqs)
        if quant is None:
            for r, r0 in zip(reqs, reqs0):
                assert results[r.request_id].tokens == \
                    golden[r0.request_id], f"{kw} diverged"
        else:
            # a quantized engine's reference is the PLAIN quantized engine
            plain = _engine(quant=quant)
            ref = plain.run(_mixed_requests(6, np.random.default_rng(2)))
            assert sorted(t.tokens for t in results.values()) == \
                sorted(t.tokens for t in ref.values()), f"{kw} diverged"


# ---------------------------------------------------------------------------
# Request(speculate=) opt-out + validation


def test_request_speculate_off_opts_out():
    """speculate="off" requests never get draft proposals: an all-off
    batch dispatches ZERO drafts (nprop=0 rides the same fused verify)
    and stays bitwise; a mixed on/off batch is bitwise too."""
    eng = _spec_engine()
    eng.run(_mixed_requests(4, np.random.default_rng(5)))  # warm traces
    before = profiler.serving_counters()
    reqs = _mixed_requests(6, np.random.default_rng(3), speculate="off")
    golden = _golden(reqs)
    results = eng.run(reqs)
    after = profiler.serving_counters()
    for r in reqs:
        assert results[r.request_id].tokens == golden[r.request_id]
    assert after["draft_dispatches"] == before["draft_dispatches"], \
        "an all-off batch must not dispatch the draft"
    assert after["verify_dispatches"] > before["verify_dispatches"]
    assert after["spec_proposed"] == before["spec_proposed"]

    mixed = _mixed_requests(6, np.random.default_rng(4))
    for i, r in enumerate(mixed):
        if i % 2:
            r.speculate = "off"
    golden = _golden(mixed)
    results = eng.run(mixed)
    for r in mixed:
        assert results[r.request_id].tokens == golden[r.request_id]


def test_stop_token_cuts_window_mid_run():
    """A stop token landing mid-accepted-run truncates the emission there
    — the tail of the accepted run is dropped, finish_reason is STOP, and
    the stream matches the plain engine's token for token."""
    prompt = np.arange(2, 9)
    probe = _engine().run([serving.Request(prompt, max_new_tokens=8)])
    stop = list(probe.values())[0].tokens[3]   # fires mid-window at k=4

    def mk():
        return serving.Request(prompt, max_new_tokens=8, eos_token_id=stop)

    r_p, r_s = mk(), mk()
    res_p = _engine().run([r_p])[r_p.request_id]
    res_s = _spec_engine().run([r_s])[r_s.request_id]
    assert res_s.tokens == res_p.tokens
    assert res_s.finish_reason == res_p.finish_reason == serving.STOP


def test_request_speculate_validation():
    with pytest.raises(ValueError, match="speculate"):
        serving.Request(np.arange(4), max_new_tokens=2, speculate="bogus")
    with pytest.raises(ValueError, match="speculate"):
        serving.Request(np.arange(4), max_new_tokens=2, speculate="on")
    # round-trips through request state (snapshot payload)
    r = serving.Request(np.arange(4), max_new_tokens=2, speculate="off")
    assert serving.Request.from_state(r.to_state()).speculate == "off"


# ---------------------------------------------------------------------------
# KV-rewind invariant


def test_kv_rewind_pool_byte_identity():
    """After mixed traffic with REAL rejections the spec engine's paged
    pool is byte-identical to a plain engine that decoded the same tokens
    one at a time: same KV bytes (minus the trash page rejected lanes
    route to), same page table, same allocator balance — rejected draft
    positions leave no trace."""
    profiler.reset_serving_counters()
    spec = _spec_engine()
    plain = _engine()
    reqs_s = _mixed_requests(8, np.random.default_rng(6))
    reqs_p = _mixed_requests(8, np.random.default_rng(6))
    res_s = spec.run(reqs_s)
    res_p = plain.run(reqs_p)
    for rs, rp in zip(reqs_s, reqs_p):
        assert res_s[rs.request_id].tokens == res_p[rp.request_id].tokens

    c = profiler.serving_counters()
    assert c["spec_proposed"] > 0
    assert c["spec_accepted"] < c["spec_proposed"], \
        "no rejections occurred — the rewind path was not exercised"

    # page 0 is the trash page rejected/padding lanes scatter to; it is
    # the ONE page allowed to diverge
    kc_s, vc_s = np.asarray(spec._kc), np.asarray(spec._vc)
    kc_p, vc_p = np.asarray(plain._kc), np.asarray(plain._vc)
    assert (kc_s[:, 1:] == kc_p[:, 1:]).all(), \
        "rejected draft KV writes survived the rewind"
    assert (vc_s[:, 1:] == vc_p[:, 1:]).all()
    assert (spec.pool.table == plain.pool.table).all()
    bal_s, bal_p = spec.pool.balance(), plain.pool.balance()
    assert bal_s == bal_p, (bal_s, bal_p)
    assert bal_s["conserved"] and bal_s["refcounts_accounted"], bal_s


def test_kv_rewind_with_prefix_sharing():
    """Rewind under CoW: prefix-shared siblings decode speculatively; the
    freed-then-reused page flow and the prefix cache registrations end up
    identical to the plain engine's."""
    rng = np.random.default_rng(7)
    base = rng.integers(0, CFG.vocab_size, 17)

    def mk():
        rng2 = np.random.default_rng(8)
        return [serving.Request(base.copy(), max_new_tokens=6),
                serving.Request(np.concatenate(
                    [base[:16], rng2.integers(0, 97, 4)]), max_new_tokens=5),
                serving.Request(base.copy(), max_new_tokens=7,
                                do_sample=True, temperature=0.9,
                                top_p=0.85, seed=23)]

    spec, plain = _spec_engine(), _engine()
    res_s, res_p = spec.run(mk()), plain.run(mk())
    assert sorted(r.tokens for r in res_s.values()) == \
        sorted(r.tokens for r in res_p.values())
    kc_s, kc_p = np.asarray(spec._kc), np.asarray(plain._kc)
    assert (kc_s[:, 1:] == kc_p[:, 1:]).all()
    assert (spec.pool.table == plain.pool.table).all()
    assert spec.pool.balance() == plain.pool.balance()


# ---------------------------------------------------------------------------
# static-executable discipline


def test_trace_freeze_under_churn():
    """One draft + one verify trace per config; admission reordering,
    slot recycling and accept/reject churn add ZERO traces."""
    eng = _spec_engine()
    eng.run(_mixed_requests(8, np.random.default_rng(9)))
    c1 = profiler.serving_counters()
    # different order, different shapes mix, residual page state
    eng.run(list(reversed(_mixed_requests(9, np.random.default_rng(10)))))
    pending = _mixed_requests(6, np.random.default_rng(11))
    res = {}
    while pending or eng.queue_depth or eng.active_slots:
        if pending:
            eng.submit(pending.pop())
        eng.step()
        res.update(eng.pop_results())
    c2 = profiler.serving_counters()
    for t in ("spec_draft_traces", "spec_verify_traces", "paged_traces",
              "write_traces"):
        assert c2[t] == c1[t], f"{t} moved under churn: {c1[t]} -> {c2[t]}"


def test_spec_traces_exactly_once_per_config():
    """A fresh batch shape traces the draft and verify executables exactly
    once each — all boundaries after the first replay them."""
    # num_slots=8 is a FRESH spec batch shape for the whole process
    before = profiler.serving_counters()
    eng = _spec_engine(num_slots=8)
    eng.run(_mixed_requests(10, np.random.default_rng(12)))
    eng.run(_mixed_requests(5, np.random.default_rng(13)))
    after = profiler.serving_counters()
    assert after["spec_draft_traces"] - before["spec_draft_traces"] == 1
    assert after["spec_verify_traces"] - before["spec_verify_traces"] == 1
    assert after["draft_dispatches"] > before["draft_dispatches"] + 1
    assert after["verify_dispatches"] > before["verify_dispatches"] + 1


def test_plain_engine_unaffected():
    """Flags-off parity: a plain engine built while spec engines run
    keeps the pre-speculation executables — zero spec traces, zero spec
    dispatches, and the paged fused-step counter moves only for ITS
    boundaries."""
    before = profiler.serving_counters()
    eng = _engine()
    assert eng.speculate_k == 0 and eng._spec is None
    reqs = _mixed_requests(5, np.random.default_rng(14))
    golden = _golden(reqs)
    results = eng.run(reqs)
    after = profiler.serving_counters()
    for r in reqs:
        assert results[r.request_id].tokens == golden[r.request_id]
    assert after["spec_draft_traces"] == before["spec_draft_traces"]
    assert after["spec_verify_traces"] == before["spec_verify_traces"]
    assert after["draft_dispatches"] == before["draft_dispatches"]
    assert after["verify_dispatches"] == before["verify_dispatches"]


# ---------------------------------------------------------------------------
# composition gates


def test_speculate_requires_single_chip():
    with pytest.raises(ValueError, match="single-chip"):
        _spec_engine(mp=2)


def test_bad_draft_source():
    with pytest.raises(Exception, match="source"):
        _spec_engine(draft_source="oracle")


# ---------------------------------------------------------------------------
# snapshot / state_dict


def test_spec_state_in_state_dict():
    eng = _spec_engine(draft_source="shallow", draft_layers=1)
    state = eng.state_dict()
    assert state["spec"] == {"speculate_k": 4, "draft_source": "shallow",
                             "draft_layers": 1,
                             "draft_params_version": eng.params_version}
    assert "spec" not in _engine().state_dict()


def test_mid_traffic_state_roundtrip_bitwise():
    """state_dict() at a boundary mid-spec-traffic, restored into a FRESH
    spec engine, resumes every stream bitwise (drafts are boundary-atomic:
    there is never pending draft state to drain)."""
    reqs = _mixed_requests(6, np.random.default_rng(15))
    golden = _golden(reqs)
    eng = _spec_engine()
    for r in reqs:
        eng.submit(r)
    for _ in range(4):
        eng.step()
    state = eng.state_dict()
    pre = eng.pop_results()
    del eng
    restored = _spec_engine().load_state_dict(state)
    results = restored.run()
    results.update(pre)
    for r in reqs:
        assert results[r.request_id].tokens == golden[r.request_id], \
            f"request {r.request_id} diverged after mid-spec restore"
    bal = restored.pool.balance()
    assert bal["conserved"] and bal["refcounts_accounted"], bal


# ---------------------------------------------------------------------------
# observability


def test_counters_and_spans_reconcile():
    """accept_rate / tokens_per_dispatch derive from the raw counters; a
    traced request's "speculate" spans reconcile with its emitted-token
    ledger: sum(emitted) == len(result.tokens) - 1 (the first token comes
    from the prefill chunk)."""
    tracing.clear()
    profiler.reset_serving_counters()
    eng = _spec_engine(trace=True)
    reqs = _mixed_requests(7, np.random.default_rng(16))
    results = eng.run(reqs)
    c = profiler.serving_counters()
    assert c["spec_proposed"] > 0 and c["verify_dispatches"] > 0
    assert c["accept_rate"] == c["spec_accepted"] / c["spec_proposed"]
    disp = c["draft_dispatches"] + c["verify_dispatches"]
    assert c["tokens_per_dispatch"] == c["spec_tokens_out"] / disp
    # every decode-emitted token is accounted to exactly one boundary span
    recs = {r["request_id"]: r for r in tracing.traces()}
    total_emitted = 0
    for r in reqs:
        spans = [s for s in recs[r.request_id]["spans"]
                 if s["name"] == "speculate"]
        assert spans, "spec boundaries must record a speculate span"
        emitted = sum(s["emitted"] for s in spans)
        assert emitted == len(results[r.request_id].tokens) - 1
        assert all(0 <= s["accepted"] <= s["proposed"] <= eng.speculate_k
                   for s in spans)
        assert all(s["emitted"] == s["accepted"] + 1 for s in spans
                   if s["emitted"])
        total_emitted += emitted
    assert c["spec_tokens_out"] == total_emitted
    assert "spec:" in profiler.serving_summary()
    tracing.clear()


def test_summary_silent_when_off():
    profiler.reset_serving_counters()
    eng = _engine()
    eng.run(_mixed_requests(3, np.random.default_rng(17)))
    assert "spec:" not in profiler.serving_summary()


# ---------------------------------------------------------------------------
# smoke-rung gates (tools_serving_smoke --spec)


def _load_smoke():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools_serving_smoke.py")
    spec = importlib.util.spec_from_file_location("tools_serving_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_spec_rung_deterministic():
    """The deterministic --spec-det sub-rung: bitwise parity per dtype
    config, accept-rate sanity on the self-draft rungs, and the
    trace-freeze gate — all without wall-clock assertions."""
    out = _load_smoke().run_spec_rung(quick=True, deterministic=True)
    assert out["parity"], out
    assert out["trace_frozen"], out
    assert out["min_accept_rate"] > 0.2, out


@pytest.mark.slow
def test_smoke_spec_rung_throughput():
    """Timed gate: backlogged speculative decode >= 1.3x plain tokens/s
    at k=4 with tokens_per_dispatch > 1.5, streams bitwise."""
    out = _load_smoke().run_spec_rung(quick=True, deterministic=False)
    assert out["parity"], out
    assert out["speedup"] >= 1.3, out
    assert out["spec"]["tokens_per_dispatch"] > 1.5, out
