"""The serving engine's bitwise gates over its block-paged KV cache
(test_serving.py holds what is the engine's and not the cache's):
  * for ANY admission order, each request's tokens are bitwise identical
    to single-request generate_from_params — greedy AND sampled, with
    chunked prefill and prefix sharing enabled;
  * prefix-shared requests (page-aligned siblings and exact-prompt
    duplicates) diverge correctly after the copy-on-write split;
  * mid-flight join/cancel/evict leaves neighbor streams bitwise-stable;
  * steady state uses a STATIC executable set (fused step at T=1 and
    T=chunk + the CoW page copy), trace-counter gated;
  * the page allocator balances (no leaks) and admission is page-aware
    (a request longer than an equal share of the pool serves fine from
    pages);
plus the stop-condition matrix and the satellites: temperature validation, recycled-slot state
reset, prefill padded-waste metric, and the Pallas kernel's interpret-mode
parity with the jnp gather path.
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu import profiler, serving
from paddle_tpu.models.generation import generate_from_params
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.gpt_hybrid import init_gpt_params

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


# shape palette whose first four are test_serving.py's (warm jit cache for
# the reference); includes prompts longer than the chunk so prefill chunking
# and page crossing are always exercised
_SHAPES = ((3, 4), (5, 6), (9, 4), (13, 6), (21, 5), (37, 4))


def _mixed_requests(n, rng, shapes=_SHAPES, **kw):
    reqs = []
    for i in range(n):
        plen, mnt = shapes[i % len(shapes)]
        reqs.append(serving.Request(rng.integers(0, CFG.vocab_size, plen),
                                    max_new_tokens=mnt, **kw))
    return reqs


# ---------------------------------------------------------------------------
# bitwise parity gates


@pytest.mark.parametrize("shapes,n", [(_SHAPES[:4], 7), (_SHAPES, 8)],
                         ids=["one-chunk", "chunked"])
def test_greedy_bitwise_parity_mixed_lengths(shapes, n):
    """Prompts of one or two chunks, and prompts of up to five."""
    eng = _engine()
    reqs = _mixed_requests(n, np.random.default_rng(0), shapes)
    results = eng.run(reqs)
    for r in reqs:
        assert results[r.request_id].tokens == \
            _ref_tokens(r.prompt, r.max_new_tokens), \
            f"request {r.request_id} diverged from single-request decode"
        assert results[r.request_id].finish_reason == serving.LENGTH


@pytest.mark.parametrize("prefill_chunk", [8, 32])
@pytest.mark.parametrize("sampling", [
    # the nucleus cut
    {"temperature": 0.8, "top_p": 0.9, "seed": 7},
    # none: the engine's traced top_p=1.0 stand-in must be bitwise
    # identical to generate's structural top_p=None skip (float32 cumsum
    # saturation used to mask tail tokens)
    {"temperature": 1.3, "seed": 11},
], ids=["top_p", "no-top_p"])
def test_sampled_stream_matches_generate(sampling, prefill_chunk):
    """Per-slot PRNG streams replicate generate's split-per-step stream, so
    even SAMPLED requests match the single-request path exactly, whether
    the prompt is one chunk of the ladder or three."""
    eng = _engine(prefill_chunk=prefill_chunk)
    prompt = np.random.default_rng(6).integers(0, CFG.vocab_size, 21)
    req = serving.Request(prompt, max_new_tokens=12, do_sample=True,
                          **sampling)
    res = eng.run([req])[req.request_id]
    assert res.tokens == _ref_tokens(prompt, 12, do_sample=True, **sampling)


def _unshared_prompts(rng):
    return [rng.integers(0, CFG.vocab_size, int(rng.integers(3, 14)))
            for _ in range(6)]


def _shared_prefix_prompts(rng):
    # prefix reuse must be output-invariant
    base = rng.integers(0, CFG.vocab_size, 17)
    return [base.copy(),
            np.concatenate([base[:8], rng.integers(0, 97, 6)]),
            rng.integers(0, CFG.vocab_size, 5),
            rng.integers(0, CFG.vocab_size, 11)]


@pytest.mark.parametrize("make_prompts,new,engine_kw", [
    (_unshared_prompts, 6, {}),
    # a pool small enough (12 usable pages) that admission WAITS on pages
    (_shared_prefix_prompts, 5, {"num_pages": 13}),
], ids=["free-pages", "page-contention"])
def test_admission_order_invariance(make_prompts, new, engine_kw):
    """The same request set in two different submission orders produces the
    same per-request tokens (slot assignment is irrelevant to output)."""
    prompts = make_prompts(np.random.default_rng(1))
    n = len(prompts)
    outs = []
    for order in (range(n), reversed(range(n))):
        eng = _engine(num_slots=2, **engine_kw)
        reqs = [serving.Request(prompts[i], max_new_tokens=new)
                for i in order]
        results = eng.run(reqs)
        outs.append({tuple(r.prompt.tolist()): results[r.request_id].tokens
                     for r in reqs})
    assert outs[0] == outs[1]
    for p, toks in outs[0].items():
        assert toks == _ref_tokens(np.asarray(p, np.int32), new)


@pytest.mark.parametrize("prefill_chunk", [8, 32])
def test_midflight_join_and_evict_keep_slots_bitwise_stable(prefill_chunk):
    """A long-running request's stream must be untouched by other requests
    joining mid-flight and by a neighbor slot being evicted."""
    eng = _engine(num_slots=3, prefill_chunk=prefill_chunk)
    long_req = serving.Request(np.arange(2, 9), max_new_tokens=24)
    victim = serving.Request(np.arange(30, 40), max_new_tokens=24)
    eng.submit(long_req)
    eng.submit(victim)
    for _ in range(4):                      # both running, mid-flight
        eng.step()
    joiners = _mixed_requests(4, np.random.default_rng(2))
    for r in joiners:
        eng.submit(r)                       # join while long_req decodes
    eng.step()
    eng.cancel(victim)                      # evict a live neighbor slot
    results = eng.run()
    assert results[victim.request_id].finish_reason == serving.CANCELLED
    assert results[long_req.request_id].tokens == \
        _ref_tokens(long_req.prompt, 24)
    for r in joiners:
        assert results[r.request_id].tokens == \
            _ref_tokens(r.prompt, r.max_new_tokens)
    # a cancel mid-PREFILL must release the slot and its pages cleanly
    in_prefill = serving.Request(np.arange(1, 40), max_new_tokens=4)
    eng.submit(in_prefill)
    eng.step()                       # first chunk issued, prefill unfinished
    assert in_prefill.state == serving.RUNNING and not in_prefill.tokens
    eng.cancel(in_prefill)
    eng.run()
    bal = eng.pool.balance()
    assert bal["conserved"] and bal["refcounts_accounted"]


# ---------------------------------------------------------------------------
# prefix sharing + copy-on-write


def test_prefix_sharing_bitwise_and_cow_divergence():
    profiler.reset_serving_counters()
    eng = _engine(num_slots=4)
    base = np.arange(1, 22)                   # 2 full pages + partial third
    r1 = serving.Request(base, max_new_tokens=6)
    res1 = eng.run([r1])[r1.request_id]
    assert res1.tokens == _ref_tokens(base, 6)

    # page-aligned sibling: same first 16 tokens, different tail
    sib = np.concatenate([base[:16], np.array([60, 61, 62, 63, 64])])
    r2 = serving.Request(sib, max_new_tokens=6)
    # exact-prompt duplicates: greedy must REPLAY r1 bitwise; sampled must
    # diverge per its own stream after the CoW split
    r3 = serving.Request(base.copy(), max_new_tokens=6)
    r4 = serving.Request(base.copy(), max_new_tokens=6, do_sample=True,
                         temperature=0.7, seed=5)
    results = eng.run([r2, r3, r4])
    assert results[r2.request_id].tokens == _ref_tokens(sib, 6)
    assert results[r3.request_id].tokens == res1.tokens
    assert results[r4.request_id].tokens == \
        _ref_tokens(base, 6, do_sample=True, temperature=0.7, seed=5)
    assert results[r4.request_id].tokens != res1.tokens

    c = profiler.serving_counters()
    assert c["prefix_hits"] >= 3
    assert c["prefix_tokens_reused"] >= 16 + 20 + 20
    assert c["cow_copies"] >= 2          # exact-dup splits + self-share
    assert c["prefix_hit_rate"] > 0
    bal = eng.pool.balance()
    assert bal["conserved"] and bal["refcounts_accounted"]


def test_live_prefix_share_cancel_leaves_owner_stable():
    """Two requests sharing cached pages CONCURRENTLY: cancelling one
    mid-flight must not perturb the other's stream (pages are refcounted,
    never stolen)."""
    eng = _engine(num_slots=2)
    base = np.arange(40, 61)
    r0 = serving.Request(base, max_new_tokens=2)
    eng.run([r0])                        # registers base's pages on release
    r1 = serving.Request(base.copy(), max_new_tokens=20)   # shares + CoW
    eng.submit(r1)
    for _ in range(3):                   # r1 decoding on shared prefix
        eng.step()
    r2 = serving.Request(base.copy(), max_new_tokens=8)    # shares too
    eng.submit(r2)
    eng.step()
    eng.cancel(r2)
    results = eng.run()
    assert results[r1.request_id].tokens == _ref_tokens(base, 20)
    assert results[r2.request_id].finish_reason == serving.CANCELLED


# ---------------------------------------------------------------------------
# executable + allocator gates


def test_steady_state_static_executable_set():
    """After warmup the fused-step trace counter freezes at 2 (token
    windows T=1 and T=chunk) and the CoW copy at <= 1 — joins, evicts,
    chunked admissions, sampling sweeps and CoW remaps are pure data.
    (num_slots=5 is unique in the suite: executables are shared ACROSS
    engines per shape, so only fresh shapes show warmup traces.)"""
    profiler.reset_serving_counters()
    eng = _engine(num_slots=5)
    eng.run(_mixed_requests(4, np.random.default_rng(3)))   # warmup
    warm = profiler.serving_counters()
    assert warm["paged_traces"] == 2
    assert warm["copy_traces"] <= 1

    rng = np.random.default_rng(4)
    reqs = []
    for i in range(7):
        reqs.append(serving.Request(
            rng.integers(0, CFG.vocab_size, int(rng.integers(3, 30))),
            max_new_tokens=5, do_sample=bool(i % 2),
            temperature=0.5 + 0.3 * i, top_p=0.7 + 0.04 * i, seed=i))
    # an exact-prompt duplicate forces prefix reuse + CoW in steady state
    reqs.append(serving.Request(reqs[0].prompt.copy(), max_new_tokens=5))
    for r in reqs:
        eng.submit(r)
    eng.step()
    eng.cancel(reqs[0] if reqs[0].state == serving.RUNNING else reqs[-1])
    eng.run()
    c = profiler.serving_counters()
    assert c["paged_traces"] == 2, "fused step re-traced in steady state"
    assert c["copy_traces"] <= 1, "page copy re-traced in steady state"
    assert c["paged_steps"] > warm["paged_steps"]
    assert c["chunk_steps"] > 0 and c["chunk_steps"] < c["paged_steps"]


def test_page_allocator_balances_no_leaks():
    """Allocator conservation through admission, sharing, CoW, eviction
    and cancellation; after draining and dropping the prefix cache every
    non-trash page is free again."""
    profiler.reset_serving_counters()
    eng = _engine(num_slots=4, num_pages=25)
    rng = np.random.default_rng(5)
    reqs = _mixed_requests(7, rng)
    reqs.append(serving.Request(reqs[0].prompt.copy(), max_new_tokens=4))
    for r in reqs:
        eng.submit(r)
    eng.step()
    running = next(r for r in reqs if r.state == serving.RUNNING)
    eng.cancel(running)
    eng.run()
    bal = eng.pool.balance()
    assert bal["conserved"], bal
    assert bal["refcounts_accounted"], bal
    assert bal["free"] + bal["in_use"] == bal["num_pages"] - 1
    eng.pool.clear_cache()
    bal = eng.pool.balance()
    assert bal["free"] == bal["num_pages"] - 1      # every page returned
    assert bal["allocated"] == bal["freed"]
    c = profiler.serving_counters()
    assert c["page_occupancy"] > 0
    assert c["pages_inuse_max"] <= 24


def test_page_aware_admission_beyond_an_equal_share():
    """The engine serves a request whose prompt+max_new exceeds an equal
    share of the pool (24 pages x 8 over 4 slots = 48 tokens a slot) —
    admission is bounded by pages, not worst-case slots."""
    paged = _engine(num_slots=4, max_seq_len=128, num_pages=25)
    long_req = serving.Request(np.arange(1, 45), max_new_tokens=16)  # 60 > 48
    shorts = [serving.Request(np.arange(2, 8), max_new_tokens=5)
              for _ in range(3)]
    results = paged.run([long_req] + shorts)
    assert results[long_req.request_id].tokens == \
        _ref_tokens(np.arange(1, 45), 16)
    for r in shorts:
        assert results[r.request_id].tokens == _ref_tokens(r.prompt, 5)
    # impossible requests still fail fast instead of wedging the queue
    with pytest.raises(ValueError):
        paged.submit(serving.Request(np.arange(1, 100), max_new_tokens=60))


def test_admission_waits_for_pages_then_proceeds():
    """With a pool too small for two lifetimes at once, the second request
    must WAIT (strict FCFS) and then serve bitwise-correctly once the
    first releases its pages."""
    eng = _engine(num_slots=2, num_pages=8, prefix_cache=False)  # 7 usable
    a = serving.Request(np.arange(1, 20), max_new_tokens=13)     # 4 pages
    b = serving.Request(np.arange(50, 70), max_new_tokens=12)    # 4 pages
    eng.submit(a)
    eng.submit(b)
    eng.step()
    assert a.state == serving.RUNNING
    assert b.state == serving.QUEUED        # 3 free pages < 4 needed
    results = eng.run()
    assert results[a.request_id].tokens == _ref_tokens(a.prompt, 13)
    assert results[b.request_id].tokens == _ref_tokens(b.prompt, 12)


# ---------------------------------------------------------------------------
# satellites


def test_recycled_slot_sampled_stream_is_bitwise_independent():
    """A recycled slot must not leak its predecessor's sampling state
    (_keys/_temp/_top_p/_do_sample are reset by _free_slot): the second
    occupant's stream is bitwise what a fresh engine would produce."""
    eng = _engine(num_slots=1)
    hot = serving.Request(np.arange(1, 6), max_new_tokens=6,
                          do_sample=True, temperature=0.3, top_p=0.8,
                          seed=13)
    eng.run([hot])
    # slot state must be fully reset after recycling
    assert eng._slots[0] is None
    assert not eng._do_sample[0] and eng._temp[0] == 1.0 \
        and eng._top_p[0] == 1.0 and not eng._keys[0].any()
    cold = serving.Request(np.arange(7, 13), max_new_tokens=6)
    res = eng.run([cold])[cold.request_id]
    assert res.tokens == _ref_tokens(np.arange(7, 13), 6)
    cold2 = serving.Request(np.arange(7, 13), max_new_tokens=6,
                            do_sample=True, temperature=0.9, seed=3)
    res = eng.run([cold2])[cold2.request_id]
    assert res.tokens == _ref_tokens(np.arange(7, 13), 6, do_sample=True,
                                     temperature=0.9, seed=3)


def test_temperature_validation():
    """do_sample with temperature <= 0 is rejected up front (it used to
    reach _mask_logits' division and sample from inf logits); greedy paths
    ignore temperature entirely and stay accepted."""
    for bad in (0.0, -1.0):
        with pytest.raises(ValueError):
            serving.Request(np.arange(4), max_new_tokens=2, do_sample=True,
                            temperature=bad)
        with pytest.raises(ValueError):
            generate_from_params(_params(), np.arange(4)[None], CFG,
                                 max_new_tokens=2, do_sample=True,
                                 temperature=bad)
    # greedy with temperature=0 passes through untouched on both entries
    eng = _engine()
    req = serving.Request(np.arange(1, 5), max_new_tokens=3, temperature=0.0)
    res = eng.run([req])[req.request_id]
    assert res.tokens == _ref_tokens(np.arange(1, 5), 3)
    out = generate_from_params(_params(), np.arange(1, 5)[None], CFG,
                               max_new_tokens=3, temperature=0.0)
    assert np.asarray(out._data)[0, 4:].tolist() == res.tokens


def test_prefill_waste_metric():
    """Padded-token waste per prefill: chunks pad only the FINAL chunk
    (< page_size tokens)."""
    profiler.reset_serving_counters()
    eng = _engine()                          # chunk == page_size == 8
    eng.run([serving.Request(np.arange(1, 14), max_new_tokens=2)])  # plen 13
    c = profiler.serving_counters()
    assert c["prefill_padded_reqs"] == 1
    assert c["prefill_padded_tokens"] == 3           # 2*8 - 13
    assert c["prefill_padded_max"] < eng.page_size
    assert "prefill-waste" in profiler.serving_summary()


@pytest.mark.parametrize("plen", [4, 21], ids=["one-chunk", "three-chunks"])
def test_stop_condition_matrix_and_deadlines(plen):
    """Stop matrix + queue-expiry, whether the first token (which may
    itself stop the request) comes from a prompt's only chunk or its
    last."""
    prompt = np.random.default_rng(9).integers(0, CFG.vocab_size, plen)
    free = _ref_tokens(prompt, 8)                 # unconstrained greedy
    eng = _engine()

    # scalar eos alias: stops at (and includes) the first eos
    k = free.index(free[3])
    r_eos = serving.Request(prompt, max_new_tokens=8, eos_token_id=free[3])
    # stop_token_ids list: earliest of several stop ids wins
    r_list = serving.Request(prompt, max_new_tokens=8,
                             stop_token_ids=[free[5], free[2]])
    # max_new_tokens cap
    r_len = serving.Request(prompt, max_new_tokens=4)
    r_one = serving.Request(prompt, max_new_tokens=1)
    dead = serving.Request(np.arange(1, 5), max_new_tokens=4, deadline_s=0.0)
    import time
    eng.submit(dead)
    time.sleep(0.01)
    results = eng.run([r_eos, r_list, r_len, r_one])

    res = results[r_eos.request_id]
    assert res.finish_reason == serving.STOP
    assert res.tokens == free[:k + 1]
    first_stop = min(free.index(free[5]), free.index(free[2]))
    res = results[r_list.request_id]
    assert res.finish_reason == serving.STOP
    assert res.tokens == free[:first_stop + 1]
    res = results[r_len.request_id]
    assert res.finish_reason == serving.LENGTH
    assert res.tokens == free[:4]
    assert results[r_one.request_id].tokens == free[:1]
    assert results[dead.request_id].finish_reason == serving.EXPIRED
    assert results[dead.request_id].tokens == []
    bal = eng.pool.balance()
    assert bal["conserved"] and bal["refcounts_accounted"]

    # max_new_tokens == 0 resolves immediately with the prompt unchanged
    r0 = serving.Request(prompt, max_new_tokens=0)
    res = eng.run([r0])[r0.request_id]
    assert res.tokens == [] and res.finish_reason == serving.LENGTH
    np.testing.assert_array_equal(res.sequence, prompt)
    with pytest.raises(ValueError):
        serving.Request(prompt, max_new_tokens=-1)


def test_prefix_cache_disabled_is_private():
    profiler.reset_serving_counters()
    eng = _engine(prefix_cache=False)
    base = np.arange(1, 22)
    r1 = serving.Request(base, max_new_tokens=5)
    r2 = serving.Request(base.copy(), max_new_tokens=5)
    results = eng.run([r1, r2])
    assert results[r1.request_id].tokens == results[r2.request_id].tokens \
        == _ref_tokens(base, 5)
    c = profiler.serving_counters()
    assert c["prefix_lookups"] == 0 and c["prefix_hits"] == 0
    assert c["cow_copies"] == 0
    assert eng.pool.cache_entries == 0


# ---------------------------------------------------------------------------
# Pallas kernel (interpret mode — the TPU path's math vs the gather path)


def test_paged_decode_kernel_matches_gather_reference():
    from paddle_tpu.serving.paged_attention import paged_decode_attention
    rng = np.random.default_rng(0)
    B, nh, d, ps, MP, P = 3, 8, 128, 8, 4, 11
    q = jnp.asarray(rng.standard_normal((B, nh, d)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((P, ps, nh, d)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((P, ps, nh, d)), jnp.float32)
    table = jnp.asarray(rng.integers(1, P, (B, MP)), jnp.int32)
    pos = jnp.asarray([5, 17, 30], jnp.int32)

    S = MP * ps
    kv_k = kc[table].reshape(B, S, nh, d)
    kv_v = vc[table].reshape(B, S, nh, d)
    mask = jnp.arange(S)[None, :] <= pos[:, None]
    scores = jnp.einsum("bhd,bshd->bhs", q, kv_k) / (d ** 0.5)
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    want = jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(scores, -1), kv_v)

    got = paged_decode_attention(q, kc, vc, table, pos, page_size=ps,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("layer,d", [
    pytest.param(0, 128, id="0"), pytest.param(2, 128, id="2"),
    pytest.param(2, 80, id="2-d80")])
def test_paged_decode_kernel_reads_a_layer_of_the_whole_pool(layer, d, quant):
    """The engine's call hands the kernel the stacked pool [L, P, ...] and
    a traced layer (the index_map addresses block (layer, page)): bitwise
    the per-layer call on kc[layer], which is the old [P, ...] entry, and
    the gather read's numbers. ``d`` 80: a head_dim-80 query against a pool
    of 128 lanes (GPT-3 2.7B's), whose pad lanes hold anything finite here:
    the query's zero lanes meet K's, the output's cut V's."""
    from paddle_tpu.serving.paged_attention import (
        paged_attention_read, paged_decode_attention,
        paged_decode_attention_q, pool_head_dim)
    rng = np.random.default_rng(3)
    L, B, nh, ps, MP, P = 3, 3, 8, 8, 4, 11
    lanes = pool_head_dim(d)
    q = jnp.asarray(rng.standard_normal((B, nh, d)), jnp.float32)
    if quant:
        kc, vc = (jnp.asarray(rng.integers(-127, 128, (L, P, ps, nh, lanes)),
                              jnp.int8) for _ in range(2))
        scales = tuple(jnp.asarray(rng.uniform(0.01, 0.1, P), jnp.float32)
                       for _ in range(2))
        fn = paged_decode_attention_q
    else:
        kc, vc = (jnp.asarray(rng.standard_normal((L, P, ps, nh, lanes)),
                              jnp.float32) for _ in range(2))
        scales = ()
        fn = paged_decode_attention
    table = jnp.asarray(rng.integers(1, P, (B, MP)), jnp.int32)
    pos = jnp.asarray([5, 17, 30], jnp.int32)

    whole = fn(q, kc, vc, table, pos, *scales, page_size=ps,
               layer=jnp.asarray(layer, jnp.int32), interpret=True)
    one = fn(q, kc[layer], vc[layer], table, pos, *scales, page_size=ps,
             interpret=True)
    assert whole.shape == (B, nh, d)
    assert (np.asarray(whole) == np.asarray(one)).all()
    want = paged_attention_read(q[:, None], kc, vc, layer, table,
                                pos[:, None], ps, False, jnp.float32,
                                *scales)[:, 0]
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# the sweep's geometry: 20 table entries a slot are two and a half of the
# kernel's steps of eight pages, so a step that is part dead, a slot that
# ends on a step's edge and one a page past it all occur
_KB, _KMP, _KPS = 4, 20, 8
_SWEEPS = {
    "first_position": [0] * _KB,
    "page_end": [_KPS - 1] * _KB,
    "second_page": [_KPS] * _KB,
    "full_table": [_KMP * _KPS - 1] * _KB,
    "one_page_beside_full": [3, _KMP * _KPS - 1, 8 * _KPS - 1, 8 * _KPS],
}


@pytest.mark.parametrize("sweep,dead,d", [
    pytest.param(name, "zero", 128, id=f"{name}-zero") for name in _SWEEPS] + [
    pytest.param("one_page_beside_full", "stale", 128,
                 id="one_page_beside_full-stale"),
    pytest.param("one_page_beside_full", "nan", 128,
                 id="one_page_beside_full-nan"),
    pytest.param("one_page_beside_full", "zero", 80,
                 id="one_page_beside_full-zero-d80"),
    pytest.param("one_page_beside_full", "nan", 80,
                 id="one_page_beside_full-nan-d80")])
@pytest.mark.parametrize("stacked", [True, False], ids=["layer", "one_layer"])
@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
def test_paged_decode_kernel_sweeps_the_live_pages(quant, stacked, sweep,
                                                   dead, d):
    """The kernel against the gather read over tables as the pool keeps
    them: a slot's live entries name pages of its own, the entries past its
    last live page are 0 (``zero``) or left over (``stale``: other slots'
    pages). ``nan`` poisons every pool page that no live position of any
    slot maps to, trash page 0 included (the values of a float pool, the
    scales of an int8 one): the kernel's output is finite and the clean
    pool's, so a dead page is not fetched, not merely masked (a masked
    NaN still poisons the context sum: 0 * NaN). ``d`` 80: the query is 80
    wide and the pool 128 lanes, its pad zeros as ``pad_lanes`` writes it."""
    from paddle_tpu.serving.paged_attention import (
        paged_attention_read, paged_decode_attention,
        paged_decode_attention_q, pool_head_dim)
    rng = np.random.default_rng(11)
    L, layer, nh = 3, 2, 8
    lanes = pool_head_dim(d)
    B, MP, ps = _KB, _KMP, _KPS
    P = 1 + B * MP
    pos = np.asarray(_SWEEPS[sweep], np.int32)
    owned = np.arange(1, P).reshape(B, MP)
    live = np.arange(MP)[None, :] <= (pos // ps)[:, None]
    table = np.where(live, owned, 0)
    if dead == "stale":
        table = np.where(live, owned, rng.integers(1, P, (B, MP)))
    unmapped = np.setdiff1d(np.arange(P), owned[live])

    q = jnp.asarray(rng.standard_normal((B, nh, d)), jnp.float32)
    if quant:
        kc, vc = (rng.integers(-127, 128, (L, P, ps, nh, lanes)).astype(
            np.int8) for _ in range(2))
        scales = [rng.uniform(0.01, 0.1, P).astype(np.float32)
                  for _ in range(2)]
        fn = paged_decode_attention_q
    else:
        kc, vc = (rng.standard_normal((L, P, ps, nh, lanes)).astype(
            np.float32) for _ in range(2))
        scales = []
        fn = paged_decode_attention
    kc[..., d:] = 0
    vc[..., d:] = 0
    # (on the host before a page is poisoned: a device array may alias
    # the numpy buffer it was made from)
    want = np.asarray(paged_attention_read(
        q[:, None], jnp.asarray(kc), jnp.asarray(vc), layer,
        jnp.asarray(table), pos[:, None], ps, False, jnp.float32,
        *map(jnp.asarray, scales))[:, 0])
    if dead == "nan":
        for a in scales or (kc, vc):
            a[(slice(None), unmapped) if a.ndim > 1 else unmapped] = np.nan
    kc, vc = jnp.asarray(kc), jnp.asarray(vc)
    args = (jnp.asarray(table), jnp.asarray(pos), *map(jnp.asarray, scales))
    if stacked:
        got = fn(q, kc, vc, *args, page_size=ps,
                 layer=jnp.asarray(layer, jnp.int32), interpret=True)
    else:
        got = fn(q, kc[layer], vc[layer], *args, page_size=ps,
                 interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_pool_pads_head_dim_to_lanes_and_nothing_sees_the_pad():
    """The device pool's last axis is head_dim padded up to 128 lanes (its
    row-major layout is then the device's default on a TPU); the pad
    holds zeros whatever was served, and a snapshot keeps the model's
    head_dim."""
    from paddle_tpu.serving.paged_attention import pool_head_dim
    assert [pool_head_dim(d) for d in (16, 80, 128, 160)] == \
        [128, 128, 128, 256]
    d = CFG.hidden_size // CFG.num_heads
    eng = _engine()
    assert eng._kc.shape[-1] == pool_head_dim(d) > d
    eng.run(_mixed_requests(4, np.random.default_rng(5)))
    kc, vc = np.asarray(eng._kc), np.asarray(eng._vc)
    assert np.abs(kc[..., :d]).max() > 0
    assert not kc[..., d:].any() and not vc[..., d:].any()
    state = eng.state_dict()
    assert state["kc"].shape == kc.shape[:-1] + (d,)
    assert (state["kc"] == kc[..., :d]).all()


@pytest.mark.parametrize("speculate_k", [0, 2], ids=["plain", "verify"])
def test_sampled_steps_counts_the_dispatches_whose_tail_drew(speculate_k):
    """``sampled_steps`` is the host's count of paged dispatches in which a
    row both emitted and sampled (the predicate of the step's cond): none
    in a greedy run, and with one sampled request among greedy ones, one
    for each dispatch that request emitted from."""
    eng = _engine(speculate_k=speculate_k)
    rng = np.random.default_rng(8)
    profiler.reset_serving_counters()
    eng.run(_mixed_requests(5, rng))
    c = profiler.serving_counters()
    assert c["paged_steps"] > 0 and c["sampled_steps"] == 0

    profiler.reset_serving_counters()
    reqs = _mixed_requests(4, rng)
    prompt = rng.integers(0, CFG.vocab_size, 19)    # three chunks: the first
    sampled = serving.Request(prompt, max_new_tokens=6, do_sample=True,
                              temperature=0.9, top_p=0.9, seed=3)
    emitted_at = []                                 # two emit nothing
    sampled.on_token = lambda *_: emitted_at.append(
        profiler.serving_counters()["paged_steps"])
    res = eng.run(reqs[:2] + [sampled] + reqs[2:])[sampled.request_id]
    c = profiler.serving_counters()
    assert res.tokens == _ref_tokens(prompt, 6, do_sample=True,
                                     temperature=0.9, top_p=0.9, seed=3)
    assert c["sampled_steps"] == len(set(emitted_at))
    assert 0 < c["sampled_steps"] <= 6 < c["paged_steps"]
    if not speculate_k:          # one token a dispatch without speculation
        assert c["sampled_steps"] == 6


@pytest.mark.parametrize("read,hidden", [
    pytest.param("gather", 256, id="gather"),
    pytest.param("kernel", 256, id="kernel"),
    pytest.param("kernel", 160, id="kernel-d80")])
def test_decode_pages_counters_follow_the_read_the_step_was_built_with(
        read, hidden, monkeypatch):
    """``decode_pages_table`` counts the table entries of every [B, 1]
    decode dispatch, ``decode_pages_swept`` those its attention read
    visits: every entry under the gather read (the CPU's), the pages each
    slot holds at its uploaded ``pos`` under the decode kernel (built here
    by a patched ``kernel_ok`` and interpreted; two heads of 128, or of 80
    in the pool's 128 lanes), and serves the single-request reference's
    tokens."""
    from paddle_tpu.serving import paged_attention as PA, served_model
    cfg = GPTConfig(vocab_size=97, hidden_size=hidden, num_layers=2,
                    num_heads=2, max_seq_len=64, dropout=0.0, use_flash=False,
                    compute_dtype="float32", remat=False)
    if read == "kernel":
        monkeypatch.setattr(served_model._GPTServed, "kernel_ok",
                            lambda *a: True)
        monkeypatch.setattr(PA, "paged_decode_attention", functools.partial(
            PA.paged_decode_attention, interpret=True))
    eng = serving.Engine(params=init_gpt_params(cfg, jax.random.key(1)),
                         config=cfg, num_slots=3, max_seq_len=64,
                         page_size=8, prefill_chunk=8)
    B, MP, ps = 3, 64 // 8, 8
    step, decodes = eng._paged_step, []

    def spy(params, kc, vc, packed, *rest, layout):
        if (layout.B, layout.T) == (B, 1):      # a copy: the buffer is
            start = layout.fields["start"]      # the engine's to refill
            decodes.append(np.array(packed)[start.offset:start.offset + B])
        return step(params, kc, vc, packed, *rest, layout=layout)
    eng._paged_step = spy
    rng = np.random.default_rng(4)
    profiler.reset_serving_counters()
    reqs = [serving.Request(rng.integers(0, cfg.vocab_size, n),
                            max_new_tokens=m)
            for n, m in ((3, 9), (13, 6), (21, 12), (5, 4))]
    served = eng.run(reqs)
    for r in reqs:
        want = np.asarray(generate_from_params(
            eng.params, np.asarray(r.prompt)[None], cfg,
            max_new_tokens=r.max_new_tokens)._data)[0, len(r.prompt):]
        assert served[r.request_id].tokens == want.tolist()
    c = profiler.serving_counters()
    assert len(decodes) == c["paged_steps"] - c["chunk_steps"] > 12
    assert c["decode_pages_table"] == len(decodes) * B * MP
    held = sum(int((pos // ps + 1).sum()) for pos in decodes)
    assert c["decode_pages_swept"] == \
        (held if read == "kernel" else c["decode_pages_table"])
    assert 0 < held < c["decode_pages_table"] / 2


# ---------------------------------------------------------------------------
# structural gate: the pool is the layer scan's carry, never its xs or ys


def _subjaxprs(obj):
    if hasattr(obj, "eqns"):
        yield obj
    elif hasattr(obj, "jaxpr"):
        yield from _subjaxprs(obj.jaxpr)
    elif isinstance(obj, (tuple, list)):
        for o in obj:
            yield from _subjaxprs(o)


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested ones (pjit, shard_map, scan, cond,
    custom calls) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _eqns(sub)


def _scans(jaxpr):
    return (eqn for eqn in _eqns(jaxpr) if eqn.primitive.name == "scan")


def _assert_pool_is_carried(closed, pool_shape):
    """``pool_shape`` [L, P, page, nh, d]: no scan may take as xs or return
    as ys the pool or a layer of it (any head count: an mp shard holds
    nh/mp), the first two outputs are the pools, and they ride one scan
    as carry."""
    L, P, page, _, d = pool_shape

    def pool_like(v):
        sh = v.aval.shape
        return len(sh) in (4, 5) and sh[-4:-2] == (P, page) and sh[-1] == d

    carried = []
    for eqn in _scans(closed.jaxpr):
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        stacked = eqn.invars[nc + nk:] + eqn.outvars[nk:]
        bad = [v.aval for v in stacked if pool_like(v)]
        assert not bad, f"a scan slices or re-stacks the pool: {bad}"
        carried.append([v.aval.shape for v in eqn.outvars[:nk]
                        if pool_like(v)])
    assert [c for c in carried if len(c) == 2 and
            all(len(sh) == 5 and sh[0] == L for sh in c)], carried
    assert [v.aval.shape for v in closed.jaxpr.outvars[:2]] == \
        [tuple(pool_shape)] * 2


def _step_jaxprs(variant, quant=None, cfg=None):
    """The engine, and the jaxprs of the step it builds at its steady-state
    shapes [B, 1] and [1, chunk] ([B, k+1] for verify). Platform
    independent: the kernel variant traces the step the engine builds on a
    TPU. ``cfg``: another model than the module's ``CFG`` (head_dim 16)."""
    from paddle_tpu.serving import engine as E
    kw = {"verify": {"speculate_k": 3}, "mp": {"mp": 2}}.get(variant, {})
    # num_slots=9 is unique in the suite: these traces warm no executable
    # that a trace-count gate of another test counts
    kw.update(num_slots=9, quant=quant)
    if cfg is None:
        cfg, eng = CFG, _engine(**kw)
    else:
        eng = serving.Engine(
            params=init_gpt_params(cfg, jax.random.key(1)), config=cfg,
            max_seq_len=cfg.max_seq_len, page_size=8, prefill_chunk=8, **kw)
    B, C, MP = 9, eng.prefill_chunk, eng.pool.table.shape[1]
    step = eng._paged_step
    if variant == "kernel":
        step = E._make_paged_step(
            E._cfg_key(cfg), eng.top_k, eng.page_size, True, (),
            quant=None if quant is None else eng._quant.key())

    def operands(b, t):
        z = lambda *sh, dt=np.int32: jnp.zeros(sh, dt)
        return (eng.params, eng._kc, eng._vc, z(b, t), z(b), z(b),
                z(b, dt=bool), z(b, MP))

    def sampling(b):
        return (jnp.zeros(b, bool), jnp.ones(b, np.float32),
                jnp.ones(b, np.float32), jnp.zeros((b, 2), np.uint32))

    if variant == "verify":
        traced = [jax.make_jaxpr(eng._spec_verify)(
            *operands(B, 4), jnp.zeros(B, np.int32), *sampling(B),
            *eng._kv_scale_args())]
    else:
        # the step on the idle operands warm_up sends (Engine._step_args)
        traced = []
        for b, t in ((B, 1), (1, C)):
            args, kw = eng._step_args(b, t)
            traced.append(jax.make_jaxpr(
                functools.partial(step, **kw))(*args))
    return eng, traced


@pytest.mark.parametrize("quant", [None, "int8"], ids=["fp", "int8"])
@pytest.mark.parametrize("variant", ["plain", "kernel", "verify", "mp"])
def test_pool_is_the_layer_scans_carry(variant, quant):
    """What made every dispatch copy the pool in and out of the scan, layer
    by layer, was the pool riding as xs and coming back as stacked ys."""
    eng, traced = _step_jaxprs(variant, quant)
    for closed in traced:
        _assert_pool_is_carried(closed, eng._kc.shape)


@pytest.mark.parametrize("variant", ["plain", "kernel", "verify", "mp"])
def test_sampling_tail_lies_in_cond_branches(variant, primitives):
    """A dispatch in which no emitting row samples pays for the argmax
    alone: in the step's jaxpr every sort (the nucleus cut) and every draw
    of random bits lies under a cond, the argmax outside; the per-slot key
    split stays outside too, so a greedy token still advances its stream."""
    _, traced = _step_jaxprs(variant)
    for closed in traced:
        found = set(primitives(closed.jaxpr))
        assert {("sort", True), ("random_bits", True),
                ("argmax", False), ("random_split", False)} <= found
        assert not {("sort", False), ("random_bits", False)} & found


def test_paged_kernel_routing_predicate(monkeypatch):
    from paddle_tpu.serving.paged_attention import paged_kernel_supported
    # off-TPU backends always fall back to the jnp gather path
    assert not paged_kernel_supported(8, 128, 16)   # cpu backend here
    assert not paged_kernel_supported(8, 64, 16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # any head_dim: the pool holds it in whole 128-lane tiles
    for d in (128, 80, 64, 96, 256):
        assert paged_kernel_supported(8, d, 16)
    assert paged_kernel_supported(32, 80, 16)       # GPT-3 2.7B
    assert paged_kernel_supported(16, 128, 16)      # GPT-3 1.3B
    assert not paged_kernel_supported(4, 128, 16)   # heads: sublane tiles
    assert not paged_kernel_supported(8, 128, 4)    # page rows
    # the K and V double buffers of a step's pages have to fit VMEM: 128
    # heads of 128 lanes are 16 MiB in bf16 (Mosaic refuses them for a
    # described v5e, and takes 96 heads' 12 MiB), 8 MiB in an int8 pool
    assert paged_kernel_supported(96, 128, 16)
    assert not paged_kernel_supported(128, 128, 16)
    assert paged_kernel_supported(128, 128, 16, itemsize=1)
    assert paged_kernel_supported(32, 80, 16, itemsize=4)
    assert not paged_kernel_supported(64, 128, 16, itemsize=4)


@pytest.mark.parametrize("hidden", [256, 160], ids=["d128", "d80"])
def test_kernel_step_pads_the_query_only_where_head_dim_is_not_the_lanes(
        hidden):
    """The [B, 1] step built with the kernel, two heads. head_dim 128 (the
    pool's lanes): around the kernel call no pad of the query and no slice
    of the context is traced (the 1.3B cell's executable is untouched by
    the other width). head_dim 80: one pad [B, nh, 80] -> [B, nh, 128] and
    one slice back, a layer. Neither gathers the pages into a window
    [B, MP, page, nh, lanes]: that is the other read."""
    cfg = GPTConfig(vocab_size=97, hidden_size=hidden, num_layers=2,
                    num_heads=2, max_seq_len=64, dropout=0.0, use_flash=False,
                    compute_dtype="float32", remat=False)
    eng, (decode, chunk) = _step_jaxprs("kernel", cfg=cfg)
    B, nh, d, lanes = 9, 2, hidden // 2, eng._kc.shape[-1]
    MP = eng.pool.table.shape[1]
    window = (B, MP, eng.page_size, nh, lanes)

    def count(closed, name, shape_in, shape_out):
        return sum(1 for eqn in _eqns(closed.jaxpr)
                   if eqn.primitive.name == name
                   and eqn.invars[0].aval.shape == shape_in
                   and eqn.outvars[0].aval.shape == shape_out)

    def names(closed):
        return [eqn.primitive.name for eqn in _eqns(closed.jaxpr)]

    assert names(decode).count("pallas_call") == 1      # in the layer scan
    assert "pallas_call" not in names(chunk)
    widened = 0 if d == lanes else 1
    assert count(decode, "pad", (B, nh, d), (B, nh, lanes)) == widened
    assert count(decode, "slice", (B, nh, lanes), (B, nh, d)) == widened
    assert not [eqn for eqn in _eqns(decode.jaxpr)
                if eqn.primitive.name == "gather"
                and eqn.outvars[0].aval.shape == window]
    # (the chunk step keeps the gather read)
    assert [eqn for eqn in _eqns(chunk.jaxpr)
            if eqn.primitive.name == "gather"
            and eqn.outvars[0].aval.shape == (1,) + window[1:]]
