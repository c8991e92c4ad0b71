"""AFMoE (models/afmoe.py) against its family's plain reference
(benchmark/families/afmoe/reference.py: float32, no cache, no ring, imports
nothing of the program), and through ``serving.Engine`` on its normal path:
window and full attention layers with grouped KV heads over a cache of two
groups of layers (the window group a ring a slot), gated attention,
sigmoid-routed experts beside a shared expert. CPU, float32, seeded random
weights, a toy width with every kind of layer: a dense window layer, then
window, window, full, window expert layers, 8 experts top-2, 4 query heads
on 2 KV heads, window 16 (benchmark/tests/rehearsal/configs/tiny-afmoe.json).
With pages of 8 and chunks of up to 32 a slot's ring is 7 pages, 56
positions: a context of 130 has gone round it twice.

Tolerance, on float32 logits of magnitude about 0.7: program and reference
do the same arithmetic in other orders (one einsum against a loop over
experts, a ring's gather against a masked full row, scans against a walk), so
they differ by float32 summation order alone: 2e-5 absolute holds twenty
times that, and a window layer that reads its whole context, a rotated full
layer, a dropped gate or a ring one page short moves logits by 1e-2 and
more."""
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import loader, reference as R  # noqa: E402
from paddle_tpu import profiler, serving  # noqa: E402
from paddle_tpu.models import afmoe as A, moe as MOE  # noqa: E402
from paddle_tpu.serving import engine as E  # noqa: E402
from paddle_tpu.serving.paged_attention import ring_key_positions  # noqa: E402
from paddle_tpu.serving.paged_kv import ring_pages  # noqa: E402

TOL = 2e-5
SEED = 2 ** 31 + 7
FAM = loader.load_family("afmoe")
with open(os.path.join(ROOT, "benchmark", "tests", "rehearsal", "configs",
                       "tiny-afmoe.json")) as _f:
    CFG = json.load(_f)
PC = FAM.sut.program_config(CFG)
PAGE, CHUNK, MAXSEQ = 8, 32, 160
RING = ring_pages(CFG["sliding_window"], CHUNK, PAGE)            # 7 pages


@pytest.fixture(scope="module")
def weights():
    return FAM.weights.make_weights(CFG, SEED, "float32")


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, CFG["vocab_size"],
                                             (3, 144)).astype(np.int32)


@pytest.fixture(scope="module")
def ref_logits(ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(FAM.reference.served_logits(
            CFG, SEED, jnp.asarray(ids), "float32", R.mm_exact))


def _engine(weights, config=PC, **kw):
    args = dict(num_slots=4, max_seq_len=MAXSEQ, page_size=PAGE,
                prefill_chunk=CHUNK)
    args.update(kw)
    return serving.Engine(params=weights, config=config, **args)


def _pools_and_tables(slots, fill=0.0):
    """Both groups' pools (filled with ``fill``: what a recycled page
    holds) and a table a group: slot b's full pages and its ring, taken
    from the pages' far end so that they are in no order."""
    geo = PC.served_model.geometry(PC)
    full, window = geo.groups
    assert full.window is None and window.window == CFG["sliding_window"]
    mp = MAXSEQ // PAGE
    pages = {"full": slots * mp + 1, "window": slots * RING + 1}
    pools = tuple(jnp.full(g.pool_shape(pages[k], PAGE), fill, jnp.float32)
                  for g, k in ((full, "full"), (window, "window"))
                  for _ in g.names)
    t_full = np.arange(slots * mp, 0, -1, dtype=np.int32).reshape(slots, mp)
    t_win = np.arange(slots * RING, 0, -1,
                      dtype=np.int32).reshape(slots, RING)
    return pools, (jnp.asarray(t_full), jnp.asarray(t_win))


def test_programs_own_tree_has_the_familys_layout(weights):
    """``init_afmoe_params`` and the benchmark family's ``make_weights``
    agree on every leaf's name and shape: one layout contract, stated twice
    because neither side may import the other."""
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
    own = jax.eval_shape(lambda k: A.init_afmoe_params(PC, k),
                         jax.random.key(0))
    assert shapes(own) == shapes(weights)


def test_forward_matches_the_reference(weights, ids, ref_logits):
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, i: A.forward(p, PC, i))(weights, ids)
    np.testing.assert_allclose(np.asarray(got), ref_logits, atol=TOL, rtol=0)


@pytest.mark.parametrize("chunk", [PAGE, CHUNK], ids=["page_chunks",
                                                      "ladder_wide_chunks"])
@pytest.mark.parametrize("plen,total", [(9, 14), (37, 44), (126, 144)],
                         ids=["under_the_window", "past_the_window",
                              "past_two_laps_of_the_ring"])
def test_chunks_then_decode_through_both_groups_match_the_full_forward(
        weights, ids, ref_logits, chunk, plen, total):
    """One slot's prompt goes through the paged forward in chunks of
    ``chunk`` (the last one padded), then token by token to ``total``: the
    full layer writes the context's pages, the window layers their ring of
    7 pages, whose entries (recycled pages full of 1e3) are masked by
    absolute position. Every logit row the step returns equals the
    reference's row of its one full forward."""
    pools, tables = _pools_and_tables(1, fill=1e3)
    step = jax.jit(lambda p, i, pl, s, v: A.paged_forward(
        p, PC, i, pl, s, v, tables, PAGE))
    row = ids[0]
    with jax.default_matmul_precision("highest"):
        for start in range(0, plen, chunk):
            valid = min(chunk, plen - start)
            win = np.zeros((1, chunk), np.int32)
            win[0, :valid] = row[start:start + valid]
            logits, pools, _ = step(weights, win, pools, jnp.asarray([start]),
                                    jnp.asarray([valid]))
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   ref_logits[0, plen - 1], atol=TOL, rtol=0)
        for pos in range(plen, total):
            logits, pools, _ = step(weights, row[None, pos:pos + 1], pools,
                                    jnp.asarray([pos]), jnp.asarray([1]))
            np.testing.assert_allclose(np.asarray(logits[0]),
                                       ref_logits[0, pos], atol=TOL, rtol=0)
    # a row is (KV heads, head_dim), not query heads; lanes whole
    assert pools[0].shape == (1, MAXSEQ // PAGE + 1, PAGE, 2, 128)
    assert pools[2].shape == (4, RING + 1, PAGE, 2, 128)


def test_slots_of_unequal_length_decode_in_one_batch(weights, ids,
                                                     ref_logits):
    """Three slots prefilled to 5, 50 and 121 positions (none, one and two
    laps of the ring begun), a fourth idle, then eight decode steps of all
    four in one [4, 1] dispatch."""
    pools, tables = _pools_and_tables(4)
    step = jax.jit(lambda p, i, pl, s, v, t: A.paged_forward(
        p, PC, i, pl, s, v, t, PAGE))
    plens = (5, 50, 121)
    with jax.default_matmul_precision("highest"):
        for b, plen in enumerate(plens):
            for start in range(0, plen, CHUNK):
                valid = min(CHUNK, plen - start)
                win = np.zeros((1, CHUNK), np.int32)
                win[0, :valid] = ids[b, start:start + valid]
                _, pools, _ = step(weights, win, pools, jnp.asarray([start]),
                                   jnp.asarray([valid]),
                                   tuple(t[b:b + 1] for t in tables))
        for i in range(8):
            pos = np.array([p + i for p in plens] + [0], np.int32)
            tok = np.array([[ids[b, pos[b]]] for b in range(3)] + [[0]],
                           np.int32)
            logits, pools, _ = step(weights, tok, pools, jnp.asarray(pos),
                                    jnp.asarray([1, 1, 1, 0]), tables)
            for b in range(3):
                np.testing.assert_allclose(
                    np.asarray(logits[b]), ref_logits[b, pos[b]], atol=TOL,
                    rtol=0)


def _served_gaps(reqs, results, config_dict, seed=SEED):
    """How far each served token's reference logit lies below the
    reference's best, by one full forward of the reference a request."""
    out = []
    for r in reqs:
        toks = results[r.request_id].tokens
        seq = np.concatenate([r.prompt, toks[:-1]]).astype(np.int32)
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(FAM.reference.served_logits(
                config_dict, seed, jnp.asarray(seq[None]), "float32",
                R.mm_exact))[0]
        p = len(r.prompt)
        out += [lg[p - 1 + i].max() - lg[p - 1 + i, t]
                for i, t in enumerate(toks)]
    return np.asarray(out)


def test_engine_serves_through_both_groups(weights, ids):
    """Through submit / step / on_token with every flag at its default but
    the sizes: three requests of 20, 61 and 130 prompt tokens in one batch.
    Every served token is the reference's best at its position, and both
    groups' allocators balance once the slots are free."""
    profiler.reset_serving_counters()
    eng = _engine(weights)
    assert eng.pool.prefix_cache_enabled is False       # resolved to off
    streamed = []
    reqs = [serving.Request(ids[0, :130], max_new_tokens=14, do_sample=False,
                            on_token=lambda _r, t: streamed.append(int(t))),
            serving.Request(ids[1, :20], max_new_tokens=30, do_sample=False),
            serving.Request(ids[2, :61], max_new_tokens=9, do_sample=False)]
    res = eng.run(reqs)
    assert streamed == res[reqs[0].request_id].tokens
    assert _served_gaps(reqs, res, CFG).max() <= TOL
    for pool in eng._group_pools:
        bal = pool.balance()
        assert bal["conserved"] and bal["refcounts_accounted"]
        assert bal["in_use"] == 0
    c = profiler.serving_counters()
    assert c["moe_layer_dispatches_decode"] > 0 and c["moe_touched_chunk"] > 0


def test_published_depth_and_pattern_at_a_toy_width():
    """2 dense + 30 expert layers, a full layer every fourth (the published
    ``layer_types``), is four segments of the same program: two dense
    window layers, seven periods of (window, full, window, window), and the
    last two. Served through the engine, its tokens are the reference's."""
    cfg = dict(CFG, num_hidden_layers=32, num_dense_layers=2)
    cfg["layer_types"] = [
        "full_attention" if (l + 1) % 4 == 0 else "sliding_attention"
        for l in range(32)]
    pc = FAM.sut.program_config(cfg)
    assert pc.layer_types == A.AfmoeConfig().layer_types
    S, F = (True, True), (True, False)
    assert A.layer_plan(pc.kinds()) == [
        (((False, True),), 2), ((S, F, S, S), 7), ((S,), 1), ((F,), 1)]
    geo = pc.served_model.geometry(pc)
    assert [(g.layers, g.window) for g in geo.groups] == [(8, None), (24, 16)]
    w = FAM.weights.make_weights(cfg, SEED, "float32")
    rng = np.random.default_rng(4)
    reqs = [serving.Request(rng.integers(0, 256, n).astype(np.int32),
                            max_new_tokens=6, do_sample=False)
            for n in (70, 11)]
    res = _engine(w, config=pc, num_slots=2).run(reqs)
    assert _served_gaps(reqs, res, cfg).max() <= TOL


@pytest.mark.parametrize("kinds,plan", [
    ("ddmmmm", [("d", 2), ("m", 4)]),
    ("dmMmmmMmmmM", [("d", 1), ("mMmm", 2), ("m", 1), ("M", 1)]),
    ("mM", [("m", 1), ("M", 1)]),
    ("mMmMmM", [("mM", 3)]),
])
def test_layer_plan_covers_the_layers_in_order(kinds, plan):
    got = A.layer_plan(list(kinds))
    assert [("".join(p), n) for p, n in got] == plan
    assert "".join("".join(p) * n for p, n in got) == kinds


def test_shares_of_the_held_experts_add_up_to_the_whole_layer(weights):
    """Four shares of two experts, with the shared expert (which every chip
    computes alike) counted once, give what the uncut reference gives for
    the whole layer: the layer is models/moe.py's, the one xing4 runs."""
    assert A.moe_ffn is MOE.moe_ffn
    p = jax.tree_util.tree_map(lambda a: a[1], weights["moe"])
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 9, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, stats = MOE.moe_ffn(p, x, PC)
        parts = [MOE.moe_ffn(p, x, PC, held=(lo, lo + 2), shared=lo == 0)
                 for lo in range(0, 8, 2)]
        p32 = {k: v for k, v in p.items() if not k.startswith("experts_")}
        xn = FAM.reference.rms(x, CFG["rms_norm_eps"], p["ffn_norm_g"])
        want = jnp.stack([FAM.reference.moe(
            p32, xn[b], CFG, R.mm_exact,
            lambda e: {k: p[k][e] for k in FAM.weights.EXPERT_LEAVES})
            for b in range(2)])
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), atol=TOL,
                               rtol=0)
    np.testing.assert_allclose(np.asarray(sum(y for y, _ in parts)),
                               np.asarray(want), atol=TOL, rtol=0)
    assert int(stats[0]) == 2 * 9 * 2                   # top-2 of 18 tokens
    assert sum(int(s[0]) for _, s in parts) == int(stats[0])


def test_ring_positions_by_hand():
    """A ring of 3 pages of 4 after position 17 is written (page 4): slot
    1 holds page 4, slot 0 page 3, slot 2 page 2; after position 5 (page
    1) slot 2 has never been written and reads as below 0."""
    got = np.asarray(ring_key_positions(3, jnp.asarray([17, 5]), 4))
    assert got[0].tolist() == [12, 13, 14, 15, 16, 17, 18, 19, 8, 9, 10, 11]
    assert got[1].tolist() == [0, 1, 2, 3, 4, 5, 6, 7, -4, -3, -2, -1]
    assert ring_pages(2048, 512, 16) == 161 and ring_pages(16, 32, 8) == RING


# ---------------------------------------------------------------------------
# the cache manager: two groups, a ring a slot


def test_window_group_is_capped_and_admission_waits_on_either_group(weights):
    """Two slots, 24 full pages: the window group holds a ring of 7 pages a
    slot however long the context. A request of 150 positions maps 19 full
    pages and 7 ring pages; a second of 40 (5 pages of each) fits beside
    it; a third of 150 waits on the FULL group; with the full group ample
    and the second request's ring held, nothing waits on the window group
    that a slot does not also bound. Release returns both groups' pages."""
    profiler.reset_serving_counters()
    eng = _engine(weights, num_slots=2, num_pages=25)
    full, window = eng._group_pools
    assert (full.slot_pages, window.slot_pages) == (MAXSEQ // PAGE, RING)
    assert window.num_pages == 2 * RING + 1
    rng = np.random.default_rng(2)
    mk = lambda n, m: serving.Request(rng.integers(0, 256, n).astype(np.int32),
                                      max_new_tokens=m, do_sample=False)
    long, short, waits = mk(140, 10), mk(30, 10), mk(120, 30)
    for r in (long, short, waits):
        eng.submit(r)
    eng.step()
    assert long.slot is not None and short.slot is not None
    assert np.count_nonzero(full.table[long.slot]) == 19
    assert np.count_nonzero(window.table[long.slot]) == RING
    assert np.count_nonzero(window.table[short.slot]) == 5
    assert (full.pages_in_use, window.pages_in_use) == (24, 12)
    assert waits.slot is None and eng.queue_depth == 1
    # the window group short, the full group ample: still no admission,
    # and nothing of the full group stays held
    held = window.try_alloc(window.free_count)
    assert not eng._try_reserve(mk(8, 8)) and full.pages_in_use == 24
    assert not eng._try_reserve(mk(8, 8), probe=True)
    window.decref(held)
    c = profiler.serving_counters()
    # layers: 1 full, 4 window; the window group uncapped would have
    # mapped 19 + 5 pages a layer
    assert c["kv_pages_mapped_full"] == 19 + 5
    assert c["kv_pages_mapped_window"] == 4 * (RING + 5)
    assert c["kv_pages_unwindowed"] == 4 * (19 + 5)
    res = eng.run()
    assert len(res) == 3
    assert _served_gaps([long, short, waits], res, CFG).max() <= TOL
    for pool in eng._group_pools:
        bal = pool.balance()
        assert bal["conserved"] and bal["refcounts_accounted"]
        assert bal["in_use"] == 0 and bal["allocated"] == bal["freed"]


def test_a_request_no_group_can_ever_hold_is_refused_at_submit(weights):
    eng = _engine(weights, num_slots=2, num_pages=12)
    with pytest.raises(ValueError, match="needs up to 15 KV pages"):
        eng.submit(serving.Request(np.arange(100), max_new_tokens=20))


def test_snapshot_carries_both_groups_and_resumes_bitwise(weights, ids):
    """A snapshot mid-decode, two laps into the ring, holds both groups'
    arrays, tables and allocators; a fresh engine resumes from it with the
    tokens the first goes on to serve, and traces nothing."""
    profiler.reset_serving_counters()
    eng = _engine(weights, num_slots=3).warm_up()      # 3 slots: fresh shapes
    warm = profiler.serving_counters()["paged_traces"]
    assert warm == 3 + 1                               # rungs 8, 16, 32; [3,1]
    r = serving.Request(ids[0, :120], max_new_tokens=20, do_sample=False)
    eng.submit(r)
    for _ in range(10):
        eng.step()
    state = eng.state_dict()
    assert state["k_window"].shape == (4, 3 * RING + 1, PAGE, 2, 16)
    assert state["v_full"].shape[0] == 1 and len(state["group_pools"]) == 1
    assert state["meta"]["group_pages"] == [3 * 20 + 1, 3 * RING + 1]
    rest = eng.run()[r.request_id].tokens
    other = _engine(weights, num_slots=3)
    other.load_state_dict(state)
    assert (other._group_pools[1].table == state["group_pools"][0]["table"]
            ).all()
    resumed = other.run()
    assert list(resumed.values())[0].tokens == rest
    assert profiler.serving_counters()["paged_traces"] == warm
    for pool in other._group_pools:
        bal = pool.balance()
        assert bal["conserved"] and bal["refcounts_accounted"]


def test_preemption_and_drain_release_both_groups(weights, ids):
    eng = _engine(weights, num_slots=2)
    r = serving.Request(ids[0, :70], max_new_tokens=20, do_sample=False)
    eng.submit(r)
    for _ in range(4):
        eng.step()
    assert all(p.pages_in_use > 0 for p in eng._group_pools)
    assert eng.drain() == [r]
    assert all(p.pages_in_use == 0 for p in eng._group_pools)


@pytest.mark.parametrize("kwargs,option", [
    ({"speculate_k": 2}, "spec"),
    ({"quant": "int8"}, "quant"),
    ({"adapter_slots": 2}, "adapters"),
    ({"mp": 2}, "mp"),
    ({"role": "prefill"}, "kv_transfer"),
    ({"role": "decode"}, "kv_transfer"),
    ({"prefix_cache": True}, "prefix_cache"),
])
def test_what_is_not_supported_raises_one_sentence(weights, kwargs, option):
    with pytest.raises(ValueError) as e:
        _engine(weights, **kwargs)
    assert f"does not serve the afmoe model with {option!r} yet" in str(e.value)


def test_decode_kernel_is_refused_with_its_reason(caplog):
    import logging
    with caplog.at_level(logging.INFO, logger="paddle_tpu.afmoe"):
        assert PC.served_model.kernel_ok(PC, 1, 16) is False
    assert "2 KV heads under 4 query heads" in caplog.text


# ---------------------------------------------------------------------------
# the step the engine builds


def _step_jaxpr(eng, b, t):
    """The jaxpr of the step the engine dispatches at [b, t], on the idle
    operands warm_up sends (Engine._step_args)."""
    args, kw = eng._step_args(b, t)
    return jax.make_jaxpr(functools.partial(eng._paged_step, **kw))(*args)


def test_both_groups_pools_are_the_layer_scans_carry(weights):
    """As for GPT and xing4: on the jaxpr of the step the engine builds, no
    scan takes a pool as xs or returns it as ys; a scan carries the pools
    of the groups its layers write (a segment with no full layer reads
    nothing of the full group) and the step returns all four first."""
    eng = _engine(weights, num_slots=7)
    shapes = [a.shape for a in eng._pools]

    def scans(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scans(sub)

    for b, t in ((7, 1), (1, CHUNK)):
        closed = _step_jaxpr(eng, b, t)
        found = list(scans(closed.jaxpr))
        assert len(found) == 4                          # layer_plan's segments
        for eqn in found:
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            xs = [v.aval.shape for v in eqn.invars[nc + nk:]]
            ys = [v.aval.shape for v in eqn.outvars[nk:]]
            assert not [s for s in xs + ys if len(s) == 5], (xs, ys)
            carried = [v.aval.shape for v in eqn.outvars[:nk]]
            assert shapes[0] in carried or shapes[2] in carried
        assert [v.aval.shape for v in closed.jaxpr.outvars[:4]] == shapes


def test_scopes_are_in_the_lowered_steps_op_names(weights):
    eng = _engine(weights, num_slots=7)
    for b, t in ((7, 1), (1, CHUNK)):
        args, kw = eng._step_args(b, t)
        text = eng._paged_step.lower(*args, **kw).as_text(debug_info=True)
        for scope in ("pt_attn_window", "pt_attn_full", "pt_attn_gate",
                      "pt_moe_route", "pt_moe_experts"):
            assert scope in text, scope


def test_window_layers_gather_the_ring_never_the_contexts_table(weights):
    """In the step's jaxpr every gather out of a window pool takes RING
    pages a slot, every gather out of a full pool the table's 20."""
    eng = _engine(weights, num_slots=7)
    closed = _step_jaxpr(eng, 7, 1)
    window_pool, full_pool = eng._pools[2].shape, eng._pools[0].shape
    seen = {"window": set(), "full": set()}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "gather":
                src = eqn.invars[0].aval.shape
                for name, shape in (("window", window_pool),
                                    ("full", full_pool)):
                    if src == shape:
                        seen[name].add(eqn.outvars[0].aval.shape[:2])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(closed.jaxpr)
    assert seen == {"window": {(7, RING)}, "full": {(7, MAXSEQ // PAGE)}}


def test_other_models_steps_take_one_table_and_one_group():
    """GPT and xing4 through the grouped geometry: one group, no window,
    one allocator, one table field in the dispatch's buffer, which the step
    unpacks to the bare ``table`` array it was (the bitwise
    and jaxpr gates of test_paged_serving.py and test_xing4_serving.py hold
    the executables themselves)."""
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.models.gpt_hybrid import init_gpt_params
    from paddle_tpu.models import xing4 as X
    gcfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=4, max_seq_len=64)
    gpt = serving.Engine(params=init_gpt_params(gcfg, jax.random.key(0)),
                         config=gcfg, num_slots=2, page_size=8,
                         prefill_chunk=16)
    xcfg = X.Xing4Config(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=2,
        first_k_dense_replace=1, n_routed_experts=4, num_experts_per_tok=2,
        num_attention_heads=2, q_lora_rank=8, kv_lora_rank=8,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, hc_mult=2)
    xing = serving.Engine(
        params=X.init_xing4_params(xcfg, jax.random.key(1)), config=xcfg,
        num_slots=2, page_size=8, prefill_chunk=16, max_seq_len=64)
    for eng, names in ((gpt, ("kc", "vc")), (xing, ("latent",))):
        (group,) = eng._geo.groups
        assert group.names == names and group.window is None
        assert eng._group_pools == [eng.pool]
        (table,) = eng._table_arg().values()
        assert table.shape == eng.pool.table.shape
        assert eng.pool.prefix_cache_enabled
        assert "group_pages" not in eng._snapshot_meta()
    profiler.reset_serving_counters()
    gpt.run([serving.Request(np.arange(1, 20), max_new_tokens=3)])
    c = profiler.serving_counters()
    assert c["kv_pages_mapped_full"] == c["kv_pages_unwindowed"] == 0


def test_engine_step_holds_no_branch_on_a_models_name():
    import inspect
    src = "".join(inspect.getsource(f) for f in (
        E.Engine.step, E.Engine._step, E.Engine._iterate_paged,
        E.Engine._prefill_chunk, E.Engine._try_reserve, E.Engine._admit,
        E.Engine._free_slot))
    for word in ("afmoe", "xing", "gpt", "GPT", "_model.name"):
        assert word not in src
