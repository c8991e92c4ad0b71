"""Quantized serving (serving/quant.py + ops/pallas_kernels/quant_gemm.py):
int8/fp8 weight-only Pallas GEMMs + the quantized paged KV pool,
calibrated through the ``quantization`` package.

Gates:
  * flags-off (bf16/bf16) engine stays bitwise identical to
    generate_from_params — the unquantized contract is untouched;
  * the exactness contract at a GIVEN dtype config: a quantized engine is
    deterministic, admission-order invariant, and mp∈{2,4} quantized
    output is bitwise identical to single-chip QUANTIZED output on the
    gspmd/ring/fused rungs (scales shard with their channels);
  * logit drift vs the fp engine is bounded for every dtype config;
  * kill-and-resume on a quantized engine is bitwise vs an uninterrupted
    quantized run (greedy AND sampled, CheckpointManager round trip), and
    a dtype-mismatched restore raises the TYPED refusal naming both
    configs instead of deserializing garbage;
  * steady state keeps the static-executable discipline (paged_traces
    frozen after warmup at every dtype config);
  * calibration bridge: quantization.PTQ observers -> QuantSpec ->
    Engine/inference.serve, with up-front shape validation naming the
    offending leaf;
  * swap_params re-quantizes on device with zero retraces;
  * memory-equal capacity: an int8 engine built from the same KV byte
    budget holds ~4x the pages and serves beyond the fp engine's
    capacity, with kv_shard_bytes()/kv_bytes_per_token() reporting the
    quantized footprint.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu import serving
from paddle_tpu.models.generation import generate_from_params
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.gpt_hybrid import init_gpt_params
from paddle_tpu.serving import metrics
from paddle_tpu.serving.quant import (
    QuantSpec, QuantSpecError, QuantDtypeMismatchError, calibrate,
    max_logit_drift,
)

# vocab 96 divides mp in {2, 4}: the quantized vocab-sharded lm head
# (head_w_s sharded over 'mp') is exercised, not just replicated
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
    kw.setdefault("prefill_chunk", 8)
    return serving.Engine(params=_params(), config=CFG, **kw)


_SHAPES = ((3, 4), (5, 6), (9, 4), (13, 6), (21, 5))


def _mixed_requests(n, rng, **kw):
    reqs = []
    for i in range(n):
        plen, mnt = _SHAPES[i % len(_SHAPES)]
        reqs.append(serving.Request(rng.integers(0, CFG.vocab_size, plen),
                                    max_new_tokens=mnt, **kw))
    return reqs


def _tok_lists(results, reqs):
    return [results[r.request_id].tokens for r in reqs]


# ---------------------------------------------------------------------------
# flags-off path untouched


def test_flags_default_bf16_and_bitwise_parity():
    """Defaults are bf16/bf16 (quant resolves to None) and the engine
    keeps the PR 13 bitwise contract with generate_from_params."""
    from paddle_tpu.flags import get_flags
    flags = get_flags()
    assert flags["FLAGS_serving_weight_dtype"] == "bf16"
    assert flags["FLAGS_serving_kv_dtype"] == "bf16"
    eng = _engine()
    assert eng._quant is None
    assert eng._kc.dtype == jnp.float32
    prompt = [1, 2, 3, 4, 5]
    res = eng.run([serving.Request(prompt, max_new_tokens=6)])
    ref = np.asarray(generate_from_params(
        _params(), np.asarray(prompt)[None], CFG,
        max_new_tokens=6)._data)[0, len(prompt):]
    assert list(res.values())[0].tokens == ref.tolist()


# ---------------------------------------------------------------------------
# exact-at-dtype-config contract


@pytest.mark.parametrize("dtype", ["int8", "fp8"])
def test_quant_engine_deterministic_and_order_invariant(dtype):
    """Same requests, two admission orders, two engines: identical token
    streams — the per-slot math is batch-independent at every dtype."""
    rng = np.random.default_rng(1)
    reqs_a = _mixed_requests(6, rng, do_sample=False)
    e1 = _engine(quant=dtype)
    out1 = _tok_lists(e1.run(reqs_a), reqs_a)

    rng = np.random.default_rng(1)
    reqs_b = _mixed_requests(6, rng, do_sample=False)
    e2 = _engine(quant=dtype)
    for r in reversed(reqs_b):                  # reversed submission order
        e2.submit(r)
    out2 = _tok_lists(e2.run(), reqs_b)
    assert out1 == out2


def test_quant_sampled_streams_deterministic():
    rng = np.random.default_rng(2)
    reqs = _mixed_requests(5, rng, do_sample=True, temperature=0.8,
                           top_p=0.9)
    states = [r.to_state() for r in reqs]
    out1 = _tok_lists(_engine(quant="int8").run(reqs), reqs)
    replay = [serving.Request.from_state(s) for s in states]
    out2 = _tok_lists(_engine(quant="int8").run(replay), replay)
    assert out1 == out2


@pytest.mark.parametrize("wd,kd", [("int8", "bf16"), ("bf16", "int8"),
                                   ("int8", "int8"), ("fp8", "fp8")])
def test_logit_drift_bounded_per_config(wd, kd):
    """Max |fp - quant| logit drift of a prefill forward stays a bounded
    fraction of the logit scale at every dtype config."""
    drift, scale = max_logit_drift(_params(), CFG, QuantSpec(wd, kd),
                                   list(range(1, 14)))
    assert drift > 0.0          # it IS quantized
    assert drift < 0.15 * max(scale, 1.0), (wd, kd, drift, scale)


def test_quant_vs_fp_greedy_tokens_mostly_agree():
    """Task-level drift: int8 weight+KV greedy streams agree with the fp
    engine on the (large) majority of tokens for this model."""
    rng = np.random.default_rng(3)
    reqs_fp = _mixed_requests(5, rng)
    fp = _tok_lists(_engine().run(reqs_fp), reqs_fp)
    rng = np.random.default_rng(3)
    reqs_q = _mixed_requests(5, rng)
    q = _tok_lists(_engine(quant="int8").run(reqs_q), reqs_q)
    total = sum(len(t) for t in fp)
    agree = sum(a == b for ft, qt in zip(fp, q) for a, b in zip(ft, qt))
    assert agree / total >= 0.6, (agree, total)


# ---------------------------------------------------------------------------
# mp: bitwise identical to single-chip QUANTIZED output


def _run_pair(quant, mp=None, comm_backend=None, sampled=True):
    rng = np.random.default_rng(4)
    kw = {}
    if mp is not None:
        kw.update(mp=mp, comm_backend=comm_backend)
    reqs = _mixed_requests(4, rng, do_sample=False) + _mixed_requests(
        2, np.random.default_rng(5), do_sample=sampled, temperature=0.7,
        top_p=0.95)
    eng = _engine(quant=quant, **kw)
    return _tok_lists(eng.run(reqs), reqs)


@pytest.mark.parametrize("mp,backend", [(2, None), (4, None), (2, "fused")])
def test_mp_quant_bitwise_vs_single_chip_quant(mp, backend):
    """The serving exactness contract at the int8 config: mp output ==
    single-chip QUANTIZED output bitwise, greedy AND sampled, on the
    default and fused rungs (scales shard with their channels; the fused
    rung dequantizes inside fused_gemm_ag's epilogue)."""
    single = _run_pair("int8")
    sharded = _run_pair("int8", mp=mp, comm_backend=backend)
    assert sharded == single


def test_mp_quant_fused_dispatches_quant_kernel():
    from paddle_tpu.ops.pallas_kernels import fused_collectives as fc
    before = fc.trace_counts().get("gemm_ag_q", 0)
    # num_slots=5 gives a dispatch shape no other test warms: the fused
    # quant kernel must trace HERE (builders/jit caches are process-wide)
    eng = _engine(quant="int8", mp=2, comm_backend="fused", num_slots=5)
    eng.run([serving.Request([1, 2, 3], max_new_tokens=2)])
    assert fc.trace_counts().get("gemm_ag_q", 0) > before
    # per-chip quantized KV bytes: 1/mp of the same-geometry int8 pool
    assert eng.kv_shard_bytes() * 2 == \
        _engine(quant="int8", num_slots=5).kv_shard_bytes()


# ---------------------------------------------------------------------------
# static-executable discipline at every dtype config


def test_quant_steady_state_trace_gate():
    """paged_traces freezes after warmup on the quantized engine: the
    scale operands are traced data, so admission/eviction/CoW/sampling
    changes never retrace (page_size=4 gives this config its own builder
    key — absolute counts are deterministic)."""
    eng = _engine(quant="int8", page_size=4, prefill_chunk=8)
    rng = np.random.default_rng(6)
    eng.run(_mixed_requests(4, rng))
    c = metrics.serving_counters()
    warm = c["paged_traces"]
    assert warm >= 2
    eng2 = _engine(quant="int8", page_size=4, prefill_chunk=8)
    eng2.run(_mixed_requests(6, np.random.default_rng(7),
                             do_sample=True, temperature=0.9))
    c2 = metrics.serving_counters()
    assert c2["paged_traces"] == warm    # a second engine adds ZERO traces


# ---------------------------------------------------------------------------
# snapshots: kill-and-resume bitwise + typed dtype refusal


@pytest.mark.parametrize("sampled", [False, True])
def test_quant_kill_and_resume_bitwise(tmp_path, sampled):
    from paddle_tpu.incubate.checkpoint import CheckpointManager
    rng = np.random.default_rng(8)
    kw = dict(do_sample=sampled)
    if sampled:
        kw.update(temperature=0.8, top_p=0.9)
    reqs = _mixed_requests(5, rng, **kw)
    states = [r.to_state() for r in reqs]

    ref_eng = _engine(quant="int8")
    ref = _tok_lists(ref_eng.run(reqs), reqs)

    replay = [serving.Request.from_state(s) for s in states]
    eng = _engine(quant="int8")
    for r in replay:
        eng.submit(r)
    for _ in range(4):                      # mid-decode, mid-prefill
        eng.step()
    mgr = CheckpointManager(str(tmp_path), async_save=False,
                            keep_last_n=2)
    eng.attach_checkpoint(mgr, every=0)
    step = eng.save_snapshot(blocking=True)
    del eng

    fresh = _engine(quant="int8")
    state = mgr.restore(step)
    fresh.load_state_dict(state)
    results = fresh.run()
    got = [results[r.request_id].tokens for r in replay
           if r.request_id in results]
    # every request resolves and matches the uninterrupted quantized run
    assert len(got) == len(replay)
    assert got == ref, f"sampled={sampled}"


def test_fp8_snapshot_roundtrip_and_run(tmp_path):
    """fp8 pools snapshot as raw bytes (numpy IO paths don't all speak
    ml_dtypes) and restore bit-exact through CheckpointManager."""
    from paddle_tpu.incubate.checkpoint import CheckpointManager
    eng = _engine(quant="fp8")
    eng.submit(serving.Request([1, 2, 3, 4, 5, 6, 7, 8, 9],
                               max_new_tokens=5))
    for _ in range(3):
        eng.step()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    eng.attach_checkpoint(mgr, every=0)
    step = eng.save_snapshot(blocking=True)
    ref = {k: v.tokens for k, v in eng.run().items()}

    fresh = _engine(quant="fp8")
    fresh.load_state_dict(mgr.restore(step))
    assert fresh._kc.dtype == jnp.float8_e4m3fn
    got = {k: v.tokens for k, v in fresh.run().items()}
    assert got == ref


def test_dtype_mismatched_restore_refused():
    """Restoring an int8 snapshot into a bf16 engine (or any other dtype
    mix) raises the TYPED refusal naming both configs — never
    deserializes garbage KV bytes."""
    qeng = _engine(quant="int8")
    qeng.submit(serving.Request([1, 2, 3], max_new_tokens=3))
    qeng.step()
    snap = qeng.state_dict()

    fp = _engine()
    with pytest.raises(QuantDtypeMismatchError) as ei:
        fp.load_state_dict(snap)
    msg = str(ei.value)
    assert "int8" in msg and "bf16" in msg
    assert ei.value.snapshot_config == ("int8", "int8")
    assert ei.value.engine_config == ("bf16", "bf16")

    # and the reverse: an fp snapshot into a quantized engine
    fp2 = _engine()
    fp2.submit(serving.Request([1, 2, 3], max_new_tokens=3))
    fp2.step()
    with pytest.raises(QuantDtypeMismatchError):
        _engine(quant="int8").load_state_dict(fp2.state_dict())
    # fp8 != int8 is a mismatch too
    with pytest.raises(QuantDtypeMismatchError):
        _engine(quant="fp8").load_state_dict(snap)


# ---------------------------------------------------------------------------
# calibration bridge + validation


def test_calibrate_produces_accepted_spec():
    spec = calibrate(_params(), CFG, sample_ids=list(range(1, 33)))
    assert spec.weight_dtype == "int8" and spec.kv_dtype == "int8"
    ws = spec.weight_scales
    assert set(ws["blocks"]) == {"qkv_w", "out_w", "up_w", "down_w"}
    assert ws["blocks"]["qkv_w"].shape == (CFG.num_layers,
                                           3 * CFG.hidden_size)
    assert ws["head_w"].shape == (CFG.vocab_size,)
    assert spec.kv_k_clip.shape == (CFG.num_layers,)
    assert (spec.kv_k_clip > 0).all() and (spec.kv_v_clip > 0).all()
    eng = _engine(quant=spec)
    res = eng.run([serving.Request([1, 2, 3, 4], max_new_tokens=4)])
    assert list(res.values())[0].tokens
    # a calibrated engine is deterministic vs itself
    res2 = _engine(quant=spec).run(
        [serving.Request([1, 2, 3, 4], max_new_tokens=4)])
    assert [r.tokens for r in res.values()] == \
        [r.tokens for r in res2.values()]


def test_calibrate_with_percentile_observer():
    from paddle_tpu.quantization import PercentileObserver
    spec = calibrate(_params(), CFG, sample_ids=list(range(1, 33)),
                     kv_observer=lambda: PercentileObserver(99.0))
    absmax = calibrate(_params(), CFG, sample_ids=list(range(1, 33)))
    # percentile clips the tail: ranges never exceed absmax ranges
    assert (spec.kv_k_clip <= absmax.kv_k_clip + 1e-12).all()
    assert _engine(quant=spec).run(
        [serving.Request([5, 6, 7], max_new_tokens=3)])


def test_spec_shape_validation_names_leaf():
    spec = calibrate(_params(), CFG, sample_ids=list(range(1, 17)))
    bad = {"blocks": dict(spec.weight_scales["blocks"]),
           "head_w": spec.weight_scales["head_w"]}
    bad["blocks"]["up_w"] = np.ones((CFG.num_layers, 3), np.float32)
    with pytest.raises(QuantSpecError, match="up_w"):
        _engine(quant=QuantSpec("int8", "int8", weight_scales=bad,
                                kv_k_clip=spec.kv_k_clip,
                                kv_v_clip=spec.kv_v_clip))
    # unknown leaf named too
    bad2 = {"blocks": dict(spec.weight_scales["blocks"]),
            "head_w": spec.weight_scales["head_w"], "wte": np.ones(4)}
    with pytest.raises(QuantSpecError, match="wte"):
        _engine(quant=QuantSpec("int8", "bf16", weight_scales=bad2))
    # wrong kv clip length named
    with pytest.raises(QuantSpecError, match="kv_k_clip"):
        _engine(quant=QuantSpec("bf16", "int8",
                                kv_k_clip=np.ones(7), kv_v_clip=np.ones(7)))
    # bad dtype string
    with pytest.raises(QuantSpecError, match="int4"):
        _engine(quant="int4")


def test_inference_serve_accepts_spec_and_rejects_bad():
    from paddle_tpu import inference
    spec = calibrate(_params(), CFG, sample_ids=list(range(1, 17)))
    eng = inference.serve(params=_params(), config=CFG, quant=spec,
                          num_slots=2, max_seq_len=64, page_size=8,
                          prefill_chunk=8)
    assert eng._quant is not None and eng._kc.dtype == jnp.int8
    bad = {"blocks": {k: np.ones((1, 1), np.float32)
                      for k in ("qkv_w", "out_w", "up_w", "down_w")},
           "head_w": np.ones(2, np.float32)}
    with pytest.raises(QuantSpecError, match="qkv_w"):
        inference.serve(params=_params(), config=CFG,
                        quant=QuantSpec("int8", "bf16", weight_scales=bad))


# ---------------------------------------------------------------------------
# hot weight swap: re-quantize on device, zero retraces


@pytest.mark.parametrize("quant", [None, "int8"])
def test_engine_stores_a_full_precision_qkv_stack_transposed(quant):
    """The single-chip engine keeps a full-precision qkv stack as the paged
    step reads it, ``qkv_wt`` [L, 3H, H], the trained stack transposed bit
    for bit; a quantized stack stays [L, H, 3H] with its per-column scale.
    A swap lands in the same form, and the full-precision engine's tree
    decodes through generate_from_params as the trained tree does."""
    eng = _engine(quant=quant, page_size=4, prefill_chunk=4)
    L, H = CFG.num_layers, CFG.hidden_size
    trained = np.asarray(_params()["blocks"]["qkv_w"])
    for version in (1, 2):
        b = eng.params["blocks"]
        if quant is None:
            assert "qkv_w" not in b and b["qkv_wt"].shape == (L, 3 * H, H)
            np.testing.assert_array_equal(np.asarray(b["qkv_wt"]),
                                          np.swapaxes(trained, 1, 2))
        else:
            assert "qkv_wt" not in b and b["qkv_w"].dtype == jnp.int8
            assert b["qkv_w"].shape == (L, H, 3 * H)
            assert b["qkv_w_s"].shape == (L, 3 * H)
        eng.swap_params(_params(), version=version + 1)
    if quant is None:
        prompt = np.asarray([[1, 2, 3, 4, 5]], np.int32)
        served, want = (generate_from_params(p, prompt, CFG, max_new_tokens=6)
                        for p in (eng.params, _params()))
        np.testing.assert_array_equal(np.asarray(served._data),
                                      np.asarray(want._data))


def test_swap_params_requantizes_zero_retraces():
    eng = _engine(quant="int8", page_size=4, prefill_chunk=4)
    eng.run([serving.Request([1, 2, 3, 4, 5], max_new_tokens=4)])
    traces = metrics.serving_counters()["paged_traces"]
    new_fp = init_gpt_params(CFG, jax.random.key(9))
    eng.swap_params(new_fp, version=2)
    assert eng.params["blocks"]["qkv_w"].dtype == jnp.int8
    res = eng.run([serving.Request([1, 2, 3, 4, 5], max_new_tokens=4)])
    assert metrics.serving_counters()["paged_traces"] == traces
    # requantization is deterministic: a fresh engine built on the new
    # weights produces the same stream
    fresh = serving.Engine(params=new_fp, config=CFG, quant="int8",
                           num_slots=3, max_seq_len=96, page_size=4,
                           prefill_chunk=4)
    res2 = fresh.run([serving.Request([1, 2, 3, 4, 5], max_new_tokens=4)])
    assert [r.tokens for r in res.values()] == \
        [r.tokens for r in res2.values()]


# ---------------------------------------------------------------------------
# fleet integration: supervisor respawn on quantized engines


def test_supervisor_kill_respawn_quantized_zero_dropped(tmp_path):
    """A replica kill on a fleet of QUANTIZED engines: the supervisor
    respawns from the last cadence snapshot (dtype config matches the
    factory's, so the typed refusal never fires) and every request
    resolves with the tokens an unkilled quantized engine produces —
    zero drops, exact at the dtype config."""
    from paddle_tpu import profiler
    from paddle_tpu.serving.supervisor import ServingSupervisor
    from paddle_tpu.utils import fault_injection as fi

    def factory():
        return _engine(quant="int8", num_slots=3)

    def traffic(seed):
        rng = np.random.default_rng(seed)
        return [serving.Request(rng.integers(0, CFG.vocab_size, 5 + 2 * i),
                                max_new_tokens=4 + (i % 3), seed=i)
                for i in range(6)]

    golden_reqs = traffic(21)
    golden = {r.request_id: t for r, t in zip(
        golden_reqs,
        _tok_lists(_engine(quant="int8", num_slots=3,
                           max_queue=16).run(golden_reqs), golden_reqs))}

    profiler.reset_serving_counters()
    reqs = traffic(21)
    id_map = dict(zip((r.request_id for r in reqs),
                      (r.request_id for r in golden_reqs)))
    sup = ServingSupervisor(factory, num_replicas=2,
                            snapshot_dir=str(tmp_path), snapshot_every=2)
    with fi.inject(fi.FaultPlan(kill_at_decode_step=3,
                                kill_engine_tag="replica0")):
        results = sup.run(reqs)
        assert fi.stats()["serving_kills"] == 1
    assert len(results) == len(reqs)
    for r in reqs:
        assert results[r.request_id].tokens == golden[id_map[r.request_id]]
    c = profiler.serving_counters()
    assert c["dropped"] == 0 and c["respawns"] >= 1


# ---------------------------------------------------------------------------
# prefix sharing / CoW on quantized pages


def test_quant_prefix_sharing_and_cow_divergence():
    """Prefix-shared siblings on a quantized pool: same prefix pages
    (quantized bytes + scales shared), divergent continuations stay
    independent, everything deterministic vs an unshared run."""
    base = list(range(1, 17))                   # two full pages at ps=8
    r1 = serving.Request(base + [20], max_new_tokens=4, seed=1)
    r2 = serving.Request(base + [30], max_new_tokens=4, seed=2)
    eng = _engine(quant="int8")
    eng.submit(r1)
    out1 = eng.run()
    eng.submit(r2)                              # prefix-hits r1's pages
    out2 = eng.run()
    hits = metrics.serving_counters()["prefix_hits"]

    solo = _engine(quant="int8", prefix_cache=False)
    s1 = solo.run([serving.Request(base + [20], max_new_tokens=4, seed=1)])
    s2 = solo.run([serving.Request(base + [30], max_new_tokens=4, seed=2)])
    assert list(out1.values())[0].tokens == list(s1.values())[0].tokens
    assert list(out2.values())[0].tokens == list(s2.values())[0].tokens
    assert hits >= 1
    bal = eng.pool.balance()
    assert bal["conserved"] and bal["refcounts_accounted"]


# ---------------------------------------------------------------------------
# memory-equal capacity + metrics


def test_memory_equal_capacity_and_dtype_bytes():
    """Same KV byte budget: the int8 pool holds 4x the fp32 pages, admits
    beyond the fp engine's page capacity, and the byte gauges report the
    quantized footprint."""
    fp = _engine(num_pages=12, num_slots=2)          # 11 usable pages
    q = _engine(num_pages=48, num_slots=2, quant="int8")
    assert q.kv_shard_bytes() <= fp.kv_shard_bytes()
    assert q.kv_bytes_per_token() * 3 < fp.kv_bytes_per_token()
    # 11 usable pages * ps 8 = 88 positions: a whole-lifetime 96-token
    # request can never fit the fp pool but fits the int8 pool
    big = lambda seed: serving.Request(
        np.random.default_rng(seed).integers(0, CFG.vocab_size, 60),
        max_new_tokens=36)
    with pytest.raises(ValueError):
        fp.submit(big(1))
    res = q.run([big(1)])
    assert len(list(res.values())[0].tokens) == 36
    c = metrics.serving_counters()
    assert c["quant_kv_bytes_per_token"] == q.kv_bytes_per_token()
    assert c["quant_scale_bytes"] > 0


def test_quant_summary_and_registry_visible():
    _engine(quant="int8").run(
        [serving.Request([1, 2, 3], max_new_tokens=2)])
    s = serving.serving_summary()
    assert "quant: w=int8 kv=int8" in s
    from paddle_tpu.observability.registry import REGISTRY
    snap = REGISTRY.snapshot()
    keys = {k for k in snap if "quant" in k}
    assert any("quant_scale_bytes" in k for k in keys)
    assert any("quant_kv_bytes_per_token" in k for k in keys)


# ---------------------------------------------------------------------------
# kernels


def test_quant_gemm_kernel_interpret_parity():
    from paddle_tpu.ops.pallas_kernels.quant_gemm import (
        quant_gemm, quant_gemm_kernel, quant_gemm_supported)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    from paddle_tpu.serving.quant import _quantize_leaf
    wq, s = _quantize_leaf(w, "int8")
    ref = quant_gemm(x, wq, s)                       # jnp epilogue
    got = quant_gemm_kernel(x, wq, s, interpret=True)
    # the kernel's k-tiled fp32 accumulation reorders the contraction
    # sum vs the one-shot jnp matmul: numerically equivalent, not
    # bitwise (the kernel is TPU-routed, never part of a bitwise gate)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-3, atol=1e-4)
    assert not quant_gemm_supported(8, 256, 256)     # CPU backend
    assert not quant_gemm_supported(8, 100, 256)


def test_paged_decode_kernel_quant_interpret_parity():
    """The quantized Pallas paged-decode kernel (dequant inside the
    online-softmax loop) matches the jnp gather read on a quantized
    pool."""
    from paddle_tpu.serving.paged_attention import (
        paged_attention_read, paged_decode_attention_q)
    rng = np.random.default_rng(12)
    B, nh, d, ps, P, MP = 2, 4, 16, 8, 9, 3
    q = jnp.asarray(rng.standard_normal((B, 1, nh, d)), jnp.float32)
    kq = jnp.asarray(rng.integers(-127, 128, (P, ps, nh, d)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (P, ps, nh, d)), jnp.int8)
    table = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    pos = jnp.asarray([[17], [9]], jnp.int32)
    ksc = jnp.asarray(rng.uniform(0.01, 0.1, P), jnp.float32)
    vsc = jnp.asarray(rng.uniform(0.01, 0.1, P), jnp.float32)
    ref = paged_attention_read(q, kq[None], vq[None], 0, table, pos, ps,
                               False, jnp.float32, ksc, vsc)
    got = paged_decode_attention_q(q[:, 0], kq, vq, table, pos[:, 0],
                                   ksc, vsc, page_size=ps,
                                   interpret=True)[:, None]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# smoke sub-rung (fast deterministic; throughput/drift gates are slow)


def _load_smoke():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tools_serving_smoke", "tools_serving_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_quant_deterministic_subrung():
    """tools_serving_smoke --quant in deterministic tiny mode: the
    memory-EQUAL int8 engine holds strictly more pages/slots from the
    same byte budget, outputs are deterministic, and max logit drift is
    bounded — no wall-clock gates (slow rung below)."""
    mod = _load_smoke()
    out = mod.run_quant_rung(quick=True, deterministic=True)
    assert out["quant"]["kv_pool_bytes"] <= out["fp"]["kv_pool_bytes"]
    assert out["quant"]["pages"] > out["fp"]["pages"]
    assert out["quant"]["slots"] >= out["fp"]["slots"]
    assert out["capacity_only_quant"]
    assert out["max_logit_drift"] < 0.15 * max(out["max_abs_logit"], 1.0)
    assert out["greedy_agreement"] >= 0.5


@pytest.mark.slow
def test_smoke_quant_memory_equal_gate():
    """Full memory-equal rung: slots x tokens/s strictly UP under int8
    weights + int8 KV from the same HBM budget, drift bounded."""
    mod = _load_smoke()
    out = mod.run_quant_rung(quick=False, deterministic=False)
    assert out["quant"]["capacity_throughput"] > \
        out["fp"]["capacity_throughput"]
    assert out["max_logit_drift"] < 0.15 * max(out["max_abs_logit"], 1.0)
