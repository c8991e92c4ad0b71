"""Continuous-batching serving engine (paddle_tpu.serving): what is the
engine's and not the KV cache's. The bitwise parity, admission-order,
join/evict and stop-condition gates live in test_paged_serving.py.

Gates here:
  * steady-state serving reuses its cached executables (one fused step a
    dispatch shape) — joins, evicts and sampling-param changes must not
    re-trace, a rebuilt engine over warm shapes traces nothing;
  * scheduler backpressure, deadlines, streaming callbacks, cancellation,
    result hand-off, impossible requests, metrics sanity;
  * the entry points (Layer, functional params, inference handoff) and the
    generation.py satellites (validation parity, traced temperature/top_p,
    stop_token_ids).
"""
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu.models.generation import generate_from_params
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
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
    return serving.Engine(params=_params(), config=CFG, **kw)


def _ref_tokens(prompt, max_new, **kw):
    """Single-request reference: generate_from_params' new-token suffix."""
    out = np.asarray(generate_from_params(_params(), np.asarray(prompt)[None],
                                          CFG, max_new_tokens=max_new,
                                          **kw)._data)
    return out[0, len(prompt):].tolist()


# Mixed-length workloads draw shapes from a small fixed palette: the
# reference `generate_from_params` compiles one program per
# (prompt_len, max_new_tokens) pair, so a palette shared across the whole
# suite keeps the jit cache warm while token CONTENT stays random (shapes
# never affect which tokens parity compares).
_SHAPES = ((3, 4), (5, 6), (9, 4), (13, 6))


def _mixed_requests(n, rng, **kw):
    reqs = []
    for i in range(n):
        plen, mnt = _SHAPES[i % len(_SHAPES)]
        reqs.append(serving.Request(rng.integers(0, CFG.vocab_size, plen),
                                    max_new_tokens=mnt, **kw))
    return reqs


# ---------------------------------------------------------------------------
# executable gates


def test_steady_state_exactly_two_executables():
    """After warmup (the fused step at [B, 1] and at the one chunk rung
    [1, 16] of the default ladder), joins/evicts and sampling-param changes
    must reuse the cached executables: the trace counter freezes.
    (num_slots=4 with pages of 16 is unique in the suite: executables are
    shared ACROSS engines per shape, so only a fresh shape shows warmup
    traces after a counter reset.)"""
    profiler.reset_serving_counters()
    eng = _engine(num_slots=4)
    eng.run(_mixed_requests(3, np.random.default_rng(3)))   # warmup
    warm = profiler.serving_counters()
    assert warm["paged_traces"] == 2

    # mixed greedy/sampled, swept sampling configs, joins + cancel
    rng = np.random.default_rng(4)
    reqs = []
    for i in range(6):
        reqs.append(serving.Request(
            rng.integers(0, CFG.vocab_size, int(rng.integers(3, 14))),
            max_new_tokens=6, do_sample=bool(i % 2),
            temperature=0.5 + 0.3 * i, top_p=0.7 + 0.04 * i, seed=i))
    for r in reqs:
        eng.submit(r)
    eng.step()
    eng.cancel(reqs[0] if reqs[0].state == serving.RUNNING else reqs[-1])
    eng.run()
    c = profiler.serving_counters()
    assert c["paged_traces"] == 2, "fused step re-traced in steady state"
    assert c["paged_steps"] > warm["paged_steps"]
    assert c["chunk_steps"] > warm["chunk_steps"]


def test_one_fused_step_trace_per_chunk_rung_used():
    """A ladder of two rungs (pages of 16, prefill_chunk=32): the fused
    step traces once a shape it is dispatched at, [B, 1], [1, 16] and
    [1, 32], and a REBUILT engine over the warm shapes traces nothing.
    (num_slots=5 with this ladder is unique in the suite.)"""
    profiler.reset_serving_counters()
    kw = dict(num_slots=5, page_size=16, prefill_chunk=32)
    eng = _engine(**kw)
    # 5 tokens ride the [1, 16] rung; 40 one [1, 32] chunk and a [1, 16] tail
    eng.generate([np.arange(1, 6), np.arange(1, 41)], max_new_tokens=3)
    assert eng._chunk_rungs == {16, 32}
    assert profiler.serving_counters()["paged_traces"] == 3
    eng2 = _engine(**kw)
    eng2.generate([np.arange(2, 42)], max_new_tokens=3)
    assert profiler.serving_counters()["paged_traces"] == 3


@pytest.mark.parametrize("name,value", [("kv_layout", "paged"),
                                        ("prefill_buckets", (16,))])
def test_the_pooled_layouts_arguments_are_gone(name, value):
    """One KV layout: the constructor takes no layout and no bucket
    ladder, not even the value that used to be the default."""
    with pytest.raises(TypeError, match=name):
        _engine(**{name: value})


# ---------------------------------------------------------------------------
# scheduler: backpressure, deadlines, streaming


def test_queue_backpressure():
    profiler.reset_serving_counters()
    eng = _engine(max_queue=2)
    for i in range(2):
        eng.submit(serving.Request(np.arange(1, 5), max_new_tokens=2))
    with pytest.raises(serving.QueueFullError):
        eng.submit(serving.Request(np.arange(1, 5), max_new_tokens=2))
    assert profiler.serving_counters()["rejected"] == 1
    eng.run()                                     # drains fine afterwards
    eng.submit(serving.Request(np.arange(1, 5), max_new_tokens=2))
    eng.run()


def test_deadline_expires_in_queue():
    eng = _engine()
    req = serving.Request(np.arange(1, 5), max_new_tokens=4, deadline_s=0.0)
    eng.submit(req)
    time.sleep(0.01)
    results = eng.run()
    assert results[req.request_id].finish_reason == serving.EXPIRED
    assert results[req.request_id].tokens == []


def test_expired_queued_request_reaped_while_slots_busy():
    """A dead queued request must be failed at the NEXT boundary even when
    no slot is free — otherwise it inflates qsize()/backpressure until a
    slot happens to drain."""
    eng = _engine(num_slots=1)
    long_req = serving.Request(np.arange(2, 9), max_new_tokens=24)
    eng.submit(long_req)
    eng.step()                                    # occupies the only slot
    doomed = serving.Request(np.arange(8, 12), max_new_tokens=4,
                             deadline_s=0.0)
    eng.submit(doomed)
    time.sleep(0.01)
    eng.step()                                    # slot still busy
    assert eng.queue_depth == 0                   # reaped, not waiting
    assert long_req.state == serving.RUNNING
    results = eng.run()
    assert results[doomed.request_id].finish_reason == serving.EXPIRED
    assert results[long_req.request_id].tokens == \
        _ref_tokens(long_req.prompt, 24)


def test_deadline_evicts_running_request():
    eng = _engine()
    req = serving.Request(np.arange(1, 5), max_new_tokens=512 // 8,
                          deadline_s=0.15)
    other = serving.Request(np.arange(20, 23), max_new_tokens=4)
    eng.submit(req)
    eng.step()                                    # admitted, running
    assert req.state == serving.RUNNING
    time.sleep(0.2)
    eng.submit(other)
    results = eng.run()
    assert results[req.request_id].finish_reason == serving.EXPIRED
    assert 0 < len(results[req.request_id].tokens) < 64
    # the neighbor admitted at the eviction boundary is unaffected
    assert results[other.request_id].tokens == _ref_tokens(other.prompt, 4)


def test_streaming_callback_and_slot_recycling():
    eng = _engine(num_slots=2)
    seen = {}
    reqs = _mixed_requests(5, np.random.default_rng(5),
                           on_token=lambda r, t: seen.setdefault(
                               r.request_id, []).append(t))
    results = eng.run(reqs)
    for r in reqs:
        assert seen[r.request_id] == results[r.request_id].tokens
    # 5 requests through 2 slots => recycling happened
    assert profiler.serving_counters()["slot_steps"] > 0


def test_on_token_callback_error_isolated():
    """A raising on_token callback must not unwind step(): the KV cache and
    PRNG keys advance before emission, so an escaping error would desync
    host _tok/_pos and re-feed stale tokens on the next step. The engine
    disables the broken callback, records the error on the result, and the
    request (and its neighbors) still finish with bitwise-parity tokens."""
    eng = _engine(num_slots=2)
    calls = []

    def bad(req, tok):
        calls.append(tok)
        if len(calls) == 2:
            raise RuntimeError("client went away")

    req = serving.Request(np.arange(1, 4), max_new_tokens=4, on_token=bad)
    other = serving.Request(np.arange(5, 9), max_new_tokens=4)
    with pytest.warns(UserWarning, match="on_token callback raised"):
        results = eng.run([req, other])
    res = results[req.request_id]
    assert res.tokens == _ref_tokens(np.arange(1, 4), 4)  # no duplicates
    assert isinstance(res.callback_error, RuntimeError)
    assert len(calls) == 2                    # callback disabled after error
    assert results[other.request_id].tokens == _ref_tokens(np.arange(5, 9), 4)
    assert results[other.request_id].callback_error is None


def test_pop_results_drains_step_loop():
    """step()-loop drivers drain via pop_results(); results are held until
    popped (and only once), so a long-running engine does not accumulate."""
    eng = _engine(num_slots=2)
    reqs = [serving.Request(np.arange(1, 4), max_new_tokens=3),
            serving.Request(np.arange(4, 8), max_new_tokens=3),
            serving.Request(np.arange(8, 10), max_new_tokens=3)]
    for r in reqs:
        eng.submit(r)
    drained = {}
    while eng.step():
        drained.update(eng.pop_results())
    drained.update(eng.pop_results())
    assert sorted(drained) == sorted(r.request_id for r in reqs)
    for r in reqs:
        assert drained[r.request_id].tokens == _ref_tokens(r.prompt, 3)
    assert eng.pop_results() == {} and eng.run() == {}


def test_cancel_queued_non_head_request():
    """Cancelling a request deep in the wait queue removes it (Request has
    identity equality — field-wise eq over numpy prompts made deque.remove
    raise and the cancel silently no-op)."""
    eng = _engine(num_slots=1)
    keeper = serving.Request(np.arange(1, 4), max_new_tokens=4)
    victim = serving.Request(np.arange(5, 8), max_new_tokens=4)
    tail = serving.Request(np.arange(9, 12), max_new_tokens=4)
    for r in (keeper, victim, tail):
        eng.submit(r)
    eng.cancel(victim)                      # not at the queue head
    assert eng.queue_depth == 2
    results = eng.run()
    res = results[victim.request_id]
    assert res.finish_reason == serving.CANCELLED and res.tokens == []
    assert results[keeper.request_id].tokens == _ref_tokens(keeper.prompt, 4)
    assert results[tail.request_id].tokens == _ref_tokens(tail.prompt, 4)


# ---------------------------------------------------------------------------
# submission


def test_submit_rejects_impossible_requests():
    eng = _engine()                               # Smax=96
    with pytest.raises(ValueError):               # prompt+new > Smax
        eng.submit(serving.Request(np.arange(10), max_new_tokens=95))
    with pytest.raises(ValueError):               # per-request top_k
        eng.submit(serving.Request(np.arange(4), max_new_tokens=2,
                                   do_sample=True, top_k=5))
    # engine-level static top_k works
    eng2 = _engine(top_k=5)
    req = serving.Request(np.arange(1, 5), max_new_tokens=4, do_sample=True,
                          top_k=5, seed=3)
    res = eng2.run([req])[req.request_id]
    assert res.tokens == _ref_tokens(np.arange(1, 5), 4, do_sample=True,
                                     top_k=5, seed=3)
    # sampled top_k=None on a top_k engine would silently draw from
    # truncated logits — rejected; greedy stays top-k-invariant
    with pytest.raises(ValueError):
        eng2.submit(serving.Request(np.arange(4), max_new_tokens=2,
                                    do_sample=True))
    greedy = serving.Request(np.arange(1, 5), max_new_tokens=4)
    res = eng2.run([greedy])[greedy.request_id]
    assert res.tokens == _ref_tokens(np.arange(1, 5), 4)
    # top_k=0 is generate's "disabled" spelling, not a conflicting value
    req0 = serving.Request(np.arange(1, 5), max_new_tokens=4, do_sample=True,
                           top_k=0, seed=3)
    res = eng.run([req0])[req0.request_id]
    assert res.tokens == _ref_tokens(np.arange(1, 5), 4, do_sample=True,
                                     seed=3)
    # empty prompt: logits would be read at the pad token
    with pytest.raises(ValueError):
        serving.Request([], max_new_tokens=4)
    # requests are single-use — including the max_new_tokens==0 fast path,
    # which must not re-resolve (and re-ledger) a finished request
    done = serving.Request(np.arange(4), max_new_tokens=0)
    eng.submit(done)
    for stale in (done, req0):
        with pytest.raises(ValueError):
            eng.submit(stale)


# ---------------------------------------------------------------------------
# metrics


def test_metrics_sanity():
    profiler.reset_serving_counters()
    eng = _engine()
    reqs = _mixed_requests(6, np.random.default_rng(6))
    results = eng.run(reqs)
    c = profiler.serving_counters()
    assert c["submitted"] == 6 and c["completed"] == 6
    assert c["tokens_out"] == sum(len(results[r.request_id].tokens)
                                  for r in reqs)
    assert c["ttft_p50"] is not None and c["ttft_p50"] > 0
    assert c["ttft_p99"] >= c["ttft_p50"]
    assert 0 < c["occupancy"] <= 1.0
    assert c["tokens_per_s"] > 0
    assert c["admitted"] == 6 and c["chunk_steps"] > 0
    for r in reqs:
        assert results[r.request_id].ttft > 0
        assert results[r.request_id].latency >= results[r.request_id].ttft
    assert "tokens/s" in profiler.serving_summary()
    # prefill-only traffic (max_new_tokens=1) emits every token from a
    # chunk dispatch, but the rate must still count
    profiler.reset_serving_counters()
    r1 = serving.Request(np.arange(1, 5), max_new_tokens=1)
    eng.run([r1])
    c = profiler.serving_counters()
    assert c["tokens_out"] == 1 and c["chunk_steps"] == 1
    assert c["tokens_per_s"] > 0


# ---------------------------------------------------------------------------
# entry points: Layer, functional params, inference handoff


def test_engine_from_layer_matches_model_generate():
    paddle.seed(0)
    model = GPTForCausalLM(CFG)
    model.eval()
    prompt = np.array([[3, 14, 15, 92]], np.int64)
    want = np.asarray(model.generate(paddle.to_tensor(prompt),
                                     max_new_tokens=6).numpy())[0, 4:]
    eng = serving.Engine(model, num_slots=2, max_seq_len=64, page_size=8,
                         prefill_chunk=8)
    res = eng.generate([prompt[0]], max_new_tokens=6)[0]
    assert res.tokens == want.tolist()


def test_head_major_params_serve_bitwise():
    """Sequence-parallel HybridTrainStep stores qkv head-major
    (config.qkv_head_major); generate_from_params and the Engine must
    permute it back to the logical split or q/k/v interleave into wrong
    heads. Head-major storage is a pure relabeling, so output is bitwise
    identical to the logical tree."""
    import dataclasses
    from paddle_tpu.distributed.tp_overlap import to_qkv_head_major
    cfg_hm = dataclasses.replace(CFG)
    cfg_hm.qkv_head_major = True
    params_hm = dict(_params())
    params_hm["blocks"] = to_qkv_head_major(
        _params()["blocks"], CFG.hidden_size, CFG.num_heads)
    prompt = np.array([3, 14, 15, 92])
    want = _ref_tokens(prompt, 6)
    got = np.asarray(generate_from_params(
        params_hm, prompt[None], cfg_hm, max_new_tokens=6)._data)
    assert got[0, 4:].tolist() == want
    eng = serving.Engine(params=params_hm, config=cfg_hm, num_slots=2,
                         max_seq_len=64, page_size=8, prefill_chunk=8)
    res = eng.generate([prompt], max_new_tokens=6)[0]
    assert res.tokens == want


def test_inference_serve_handoff():
    from paddle_tpu import inference
    eng = inference.serve(params=_params(), config=CFG, num_slots=2,
                          max_seq_len=64, page_size=8, prefill_chunk=8)
    prompt = np.array([7, 8, 9])
    res = eng.generate([prompt], max_new_tokens=4)[0]
    assert res.tokens == _ref_tokens(prompt, 4)


def test_predictor_serve_handoff(tmp_path):
    from paddle_tpu import inference
    from paddle_tpu.static import InputSpec
    paddle.seed(0)
    model = GPTForCausalLM(CFG)
    model.eval()
    prefix = str(tmp_path / "gpt")
    inference.save_inference_model(prefix, model,
                                   [InputSpec([1, 8], "int64", "ids")])
    pred = inference.load_inference_model(prefix)
    eng = pred.serve(CFG, num_slots=2, max_seq_len=64, page_size=8,
                     prefill_chunk=8)
    prompt = np.array([[3, 14, 15, 92]], np.int64)
    want = np.asarray(model.generate(paddle.to_tensor(prompt),
                                     max_new_tokens=6).numpy())[0, 4:]
    res = eng.generate([prompt[0]], max_new_tokens=6)[0]
    assert res.tokens == want.tolist()
    # non-GPT artifacts are refused with guidance
    mlp = paddle.nn.Sequential(paddle.nn.Linear(4, 4))
    prefix2 = str(tmp_path / "mlp")
    inference.save_inference_model(prefix2, mlp,
                                   [InputSpec([1, 4], "float32", "x")])
    with pytest.raises(ValueError):
        inference.load_inference_model(prefix2).serve(CFG)


# ---------------------------------------------------------------------------
# generation.py satellites


def test_generate_from_params_validation_parity():
    prompt = np.array([[7, 8, 9]])
    z = generate_from_params(_params(), prompt, CFG, max_new_tokens=0)
    np.testing.assert_array_equal(np.asarray(z._data), prompt)
    with pytest.raises(ValueError):
        generate_from_params(_params(), prompt, CFG, max_new_tokens=-1)


def test_traced_sampling_no_recompile():
    """Sweeping temperature/top_p reuses ONE executable (they are traced
    operands now); disabling top_p (None) is a structural change and may
    retrace, but further temperature sweeps there reuse too."""
    from paddle_tpu.models import generation as G
    ids = np.array([[3, 14, 15, 9]])
    G.generate_from_params(_params(), ids, CFG, max_new_tokens=3,
                           do_sample=True, temperature=1.0, top_p=0.9)
    t0 = G._gen_traces
    for t, p in [(0.6, 0.8), (0.9, 0.85), (1.4, 0.99)]:
        G.generate_from_params(_params(), ids, CFG, max_new_tokens=3,
                               do_sample=True, temperature=t, top_p=p, seed=2)
    assert G._gen_traces == t0, "sampling-config sweep recompiled"
    for t in (0.7, 1.1):
        G.generate_from_params(_params(), ids, CFG, max_new_tokens=3,
                               do_sample=True, temperature=t, top_p=None)
    assert G._gen_traces <= t0 + 1, "temperature sweep recompiled"


def test_traced_sampling_bitwise_matches_static_path():
    """The traced temperature/top_p math must be bitwise identical to the
    old static path — reconstructed here by baking the values as Python
    constants into a fresh jit (XLA constant-folds them, exactly what
    static hash-key operands compiled to)."""
    from functools import partial
    from paddle_tpu.models import generation as G
    params = _params()
    ids = jnp.asarray([[5, 17, 33, 2, 9]], jnp.int32)
    temperature, top_p, new = 0.8, 0.9, 6
    cfg_key = (CFG.num_heads, CFG.num_layers, CFG.hidden_size,
               CFG.layer_norm_epsilon, CFG.compute_dtype)

    @partial(jax.jit, static_argnames=("cfg",))
    def static_path(params, ids, key, *, cfg):
        config = G._cfg_view(cfg)
        B, P = ids.shape
        kc, vc = G._alloc_cache(config, B, P + new)
        logits, kc, vc = G._forward_cached(params, config, ids, kc, vc, 0)
        key, sub = jax.random.split(key)
        tok = G._select_token(logits, sub, True, temperature, None, top_p)

        def step(carry, i):
            kc, vc, tok, key = carry
            key, sub = jax.random.split(key)
            logits, kc, vc = G._forward_cached(params, config, tok[:, None],
                                               kc, vc, P + i)
            nxt = G._select_token(logits, sub, True, temperature, None, top_p)
            return (kc, vc, nxt, key), tok

        (kc, vc, last, key), toks = jax.lax.scan(
            step, (kc, vc, tok, key), jnp.arange(new - 1))
        return jnp.concatenate([toks.T, last[:, None]], axis=1)

    want = np.asarray(static_path(params, ids, jax.random.key(11),
                                  cfg=cfg_key))
    got = np.asarray(G.generate_from_params(
        params, ids, CFG, max_new_tokens=new, do_sample=True,
        temperature=temperature, top_p=top_p, seed=11)._data)[:, 5:]
    np.testing.assert_array_equal(got, want)


def test_stop_token_ids_generalizes_eos():
    paddle.seed(0)
    model = GPTForCausalLM(CFG)
    model.eval()
    prompt = np.array([[1, 2]], np.int64)
    free = np.asarray(model.generate(paddle.to_tensor(prompt),
                                     max_new_tokens=8).numpy())[0, 2:]
    stop = int(free[2])
    # scalar alias and single-element list are bitwise identical
    a = np.asarray(model.generate(paddle.to_tensor(prompt), max_new_tokens=8,
                                  eos_token_id=stop).numpy())
    b = np.asarray(model.generate(paddle.to_tensor(prompt), max_new_tokens=8,
                                  stop_token_ids=[stop]).numpy())
    np.testing.assert_array_equal(a, b)
    # a later stop id in the list still freezes the row from its hit onward
    later = int(free[4])
    c = np.asarray(model.generate(paddle.to_tensor(prompt), max_new_tokens=8,
                                  stop_token_ids=[stop, later]).numpy())[0, 2:]
    assert (c[2:] == stop).all()
    # functional entry accepts the list too
    d = np.asarray(generate_from_params(
        _params(), np.array([[1, 2]]), CFG, max_new_tokens=6,
        stop_token_ids=[3, 5]).numpy())
    assert d.shape == (1, 8)
