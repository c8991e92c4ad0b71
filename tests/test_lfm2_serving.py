"""LFM2-MoE (models/lfm2.py) against its family's plain reference
(benchmark/families/lfm2/reference.py: float32, no cache, no state carried
from anywhere, imports nothing of the program), and through
``serving.Engine`` on its normal path: gated short-convolution layers whose
state a slot (the last two rows of ``u``) lives beside the paged K and V of
the attention layers, grouped KV heads, sigmoid-routed experts with no
shared expert. CPU, float32, seeded random weights, a toy width with every
kind of layer: a dense conv layer, then attention, conv, conv, conv expert
layers twice, 8 experts top-2, 4 query heads on 2 KV heads of 16
(benchmark/tests/rehearsal/configs/tiny-lfm2.json). Pages of 8, chunks of up
to 32: a prompt of 126 crosses three chunk boundaries and its last chunk is
padded.

Tolerance, on float32 logits of magnitude about 1.5: program and reference
do the same arithmetic in other orders (one einsum against a loop over
experts, a state carried across dispatches against one convolution over the
row, scans against a walk), so they differ by float32 summation order alone:
2e-5 absolute holds fifty times that, and a state that is not zeroed, is
taken from a padded row, or moves under an idle slot shifts logits by 1e-2
and more."""
import functools
import hashlib
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

import paddle_tpu as paddle  # noqa: E402
from benchmark.harness import loader, reference as R  # noqa: E402
from paddle_tpu import profiler, serving  # noqa: E402
from paddle_tpu.models import afmoe as A, lfm2 as L, moe as MOE  # noqa: E402
from paddle_tpu.models import xing4 as X  # noqa: E402
from paddle_tpu.serving import engine as E  # noqa: E402

TOL = 2e-5
SEED = 2 ** 31 + 7
FAM = loader.load_family("lfm2")
with open(os.path.join(ROOT, "benchmark", "tests", "rehearsal", "configs",
                       "tiny-lfm2.json")) as _f:
    CFG = json.load(_f)
PC = FAM.sut.program_config(CFG)
PAGE, CHUNK, MAXSEQ = 8, 32, 160
H, KEEP = CFG["hidden_size"], CFG["conv_L_cache"] - 1


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
    """K and V pools and the state (filled with ``fill``: what a recycled
    page, or a slot's last occupant, left), a page table whose pages are in
    no order, and the slots' numbers."""
    paged, state = PC.served_model.geometry(PC).groups
    assert paged.paged and not state.paged and state.row == (KEEP, H)
    mp = MAXSEQ // PAGE
    pools = tuple(jnp.full(paged.pool_shape(slots * mp + 1, PAGE), fill,
                           jnp.float32) for _ in paged.names) \
        + (jnp.full(state.state_shape(slots), fill, jnp.float32),)
    table = np.arange(slots * mp, 0, -1, dtype=np.int32).reshape(slots, mp)
    return pools, (jnp.asarray(table), jnp.arange(slots, dtype=jnp.int32))


def test_programs_own_tree_has_the_familys_layout(weights):
    """``init_lfm2_params`` and the benchmark family's ``make_weights``
    agree on every leaf's name and shape: one layout contract, stated twice
    because neither side may import the other."""
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
    own = jax.eval_shape(lambda k: L.init_lfm2_params(PC, k),
                         jax.random.key(0))
    assert shapes(own) == shapes(weights)


def test_published_configuration_by_its_own_keys():
    """The benchmark's configuration file resolves to the published widths:
    head_dim 64 from hidden / heads, theta out of ``rope_parameters``, the
    published pattern by default and its entries 1 to 5 as cut."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-24B-A2B.json")) as f:
        pc = FAM.sut.program_config(json.load(f))
    assert (pc.head_dim, pc.rope_theta, pc.route_norm_eps) == (64, 1e6, 1e-6)
    assert pc.layer_types == L.PUBLISHED_LAYER_TYPES[1:6]
    assert L.Lfm2Config().layer_types.count(L.FULL) == 10
    assert [(g.names, g.layers, g.row, g.paged)
            for g in pc.served_model.geometry(pc).groups] == [
        (("k", "v"), 1, (8, 64), True), (("u",), 4, (2, 2048), False)]
    C, F = (True, True), (True, False)
    assert MOE.layer_plan(pc.kinds()) == [(((False, True),), 1), ((F,), 1),
                                          ((C,), 3)]
    assert MOE.layer_plan(L.Lfm2Config().kinds()) == [
        (((False, True),), 2), ((F, C, C, C), 9), ((F,), 1), ((C,), 1)]


def test_forward_matches_the_reference(weights, ids, ref_logits):
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, i: L.forward(p, PC, i))(weights, ids)
    np.testing.assert_allclose(np.asarray(got), ref_logits, atol=TOL, rtol=0)


@pytest.mark.parametrize("chunk", [PAGE, CHUNK], ids=["page_chunks",
                                                      "ladder_wide_chunks"])
@pytest.mark.parametrize("plen,total", [(9, 14), (37, 44), (126, 144)],
                         ids=["inside_one_chunk", "across_a_boundary",
                              "across_three_boundaries"])
def test_chunks_then_decode_through_pages_and_state_match_the_full_forward(
        weights, ids, ref_logits, chunk, plen, total):
    """One slot's prompt goes through the paged forward in chunks of
    ``chunk`` (the last one padded past ``valid``), then token by token to
    ``total``. The slot's state starts as what its last occupant left (1e3
    everywhere): the step makes it zero where ``start`` is 0, carries it
    from one chunk to the next, and takes the last two REAL rows of a padded
    chunk. Every logit row equals the reference's row of its one full
    forward, and after each dispatch the state is the last two real rows."""
    pools, tables = _pools_and_tables(1, fill=1e3)
    step = jax.jit(lambda p, i, pl, s, v: L.paged_forward(
        p, PC, i, pl, s, v, tables, PAGE))
    row = ids[0]
    with jax.default_matmul_precision("highest"):
        for start in range(0, plen, chunk):
            valid = min(chunk, plen - start)
            win = np.zeros((1, chunk), np.int32)
            win[0, :valid] = row[start:start + valid]
            logits, pools, _ = step(weights, win, pools, jnp.asarray([start]),
                                    jnp.asarray([valid]))
            assert np.abs(np.asarray(pools[2])).max() < 10   # no 1e3 left
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   ref_logits[0, plen - 1], atol=TOL, rtol=0)
        for pos in range(plen, total):
            logits, pools, _ = step(weights, row[None, pos:pos + 1], pools,
                                    jnp.asarray([pos]), jnp.asarray([1]))
            np.testing.assert_allclose(np.asarray(logits[0]),
                                       ref_logits[0, pos], atol=TOL, rtol=0)
    # K and V rows are (KV heads, head_dim); the state a row a slot; lanes
    # whole in both
    assert pools[0].shape == (2, MAXSEQ // PAGE + 1, PAGE, 2, 128)
    assert pools[2].shape == (7, 1, KEEP, 128)


def test_a_slot_that_a_dispatch_does_not_advance_keeps_its_state(
        weights, ids, ref_logits):
    """Three slots prefilled to 5, 50 and 121 positions and a fourth half
    way through its prompt (``start`` 0 in a decode dispatch, as the engine
    passes a prefilling slot's position), then eight decode steps of all
    four in one [4, 1] dispatch with the fourth inert (``valid`` 0): its
    state is bit for bit what its chunk left, and its prompt's second chunk
    afterwards ends on the reference's logits."""
    pools, tables = _pools_and_tables(4)
    step = jax.jit(lambda p, i, pl, s, v, t: L.paged_forward(
        p, PC, i, pl, s, v, t, PAGE))
    one = lambda b: tuple(t[b:b + 1] for t in tables)
    plens = (5, 50, 121)

    def chunk(b, row, start, valid, pools):
        win = np.zeros((1, CHUNK), np.int32)
        win[0, :valid] = ids[row, start:start + valid]
        return step(weights, win, pools, jnp.asarray([start]),
                    jnp.asarray([valid]), one(b))

    with jax.default_matmul_precision("highest"):
        for b, plen in enumerate(plens):
            for start in range(0, plen, CHUNK):
                _, pools, _ = chunk(b, b, start, min(CHUNK, plen - start),
                                    pools)
        _, pools, _ = chunk(3, 0, 0, CHUNK, pools)      # row 0 again, slot 3
        held = np.array(pools[2][:, 3])
        assert np.abs(held).max() > 0
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
            assert (np.asarray(pools[2][:, 3]) == held).all()
        logits, pools, _ = chunk(3, 0, CHUNK, 9, pools)
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   ref_logits[0, CHUNK + 8], atol=TOL, rtol=0)


def _served_gaps(reqs, results, config_dict=CFG, seed=SEED):
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


def test_engine_serves_through_pages_and_state(weights, ids):
    """Through submit / step / on_token with every flag at its default but
    the sizes: three requests of 20, 61 and 130 prompt tokens in one batch
    of 4 slots. Every served token is the reference's best at its position,
    the allocator balances, and admission counted what it bound: two
    attention layers' pages of K and V rows (2 x 2 x 16 float32 a
    position) and seven conv layers' two rows of 64 a slot, against nine
    layers' pages."""
    profiler.reset_serving_counters()
    eng = _engine(weights)
    assert eng.pool.prefix_cache_enabled is False       # resolved to off
    assert len(eng._group_pools) == 1 and len(eng._pools) == 3
    assert eng._pools[2].shape == (7, 4, KEEP, 128)
    streamed = []
    reqs = [serving.Request(ids[0, :130], max_new_tokens=14, do_sample=False,
                            on_token=lambda _r, t: streamed.append(int(t))),
            serving.Request(ids[1, :20], max_new_tokens=30, do_sample=False),
            serving.Request(ids[2, :61], max_new_tokens=9, do_sample=False)]
    res = eng.run(reqs)
    assert streamed == res[reqs[0].request_id].tokens
    assert _served_gaps(reqs, res).max() <= TOL
    bal = eng.pool.balance()
    assert bal["conserved"] and bal["refcounts_accounted"]
    assert bal["in_use"] == 0
    c = profiler.serving_counters()
    assert c["moe_layer_dispatches_decode"] > 0 and c["moe_touched_chunk"] > 0
    pages = 18 + 7 + 9                          # 144, 50 and 70 positions
    page_bytes = PAGE * 2 * 2 * 16 * 4
    assert c["state_slots_bound"] == 3
    assert c["cache_bytes_bound"] == 2 * pages * page_bytes \
        + 3 * 7 * KEEP * H * 4
    assert c["cache_bytes_all_paged"] == 9 * pages * page_bytes
    # what a token costs is the paged layers' rows alone
    assert eng.kv_bytes_per_token() == 2 * 2 * 2 * 128 * 4


def test_a_reused_slot_starts_from_zero_and_recompute_is_bitwise(weights,
                                                                 ids):
    """One slot: a long request, then a short one in the slot it left (its
    state starts from zero, by the step alone: the host resets nothing),
    then a best-effort request pre-empted half way by an urgent one and
    recomputed from its first chunk. Every token is the reference's best,
    and the pre-empted request's are those of an uninterrupted run."""
    paddle.set_flags({"FLAGS_serving_preempt_margin_s": 60.0})
    try:
        eng = _engine(weights, num_slots=1, priority=True)
        mk = lambda row, n, m, **kw: serving.Request(
            ids[row, :n], max_new_tokens=m, do_sample=False, **kw)
        long, short = mk(0, 100, 6), mk(1, 11, 12)
        res = eng.run([long, short])
        assert np.abs(np.asarray(eng._pools[2])).max() > 0
        victim = mk(2, 40, 16, priority="best_effort")
        eng.submit(victim)
        for _ in range(6):
            eng.step()
        assert victim.tokens
        urgent = mk(1, 30, 3, priority="interactive", deadline_s=50.0)
        eng.submit(urgent)
        res.update(eng.run())
        assert profiler.serving_counters()["preempted"] >= 1
    finally:
        paddle.set_flags({"FLAGS_serving_preempt_margin_s": 0.0})
    assert _served_gaps([long, short, victim, urgent], res).max() <= TOL
    alone = _engine(weights, num_slots=1).run([mk(2, 40, 16)])
    assert res[victim.request_id].tokens == list(alone.values())[0].tokens


def test_published_depth_and_pattern_at_a_toy_width():
    """2 dense + 38 expert layers in the published ``layer_types`` are four
    segments of the same program; served through the engine, its tokens are
    the reference's."""
    cfg = dict(CFG, num_hidden_layers=40, num_dense_layers=2,
               layer_types=list(L.PUBLISHED_LAYER_TYPES))
    pc = FAM.sut.program_config(cfg)
    geo = pc.served_model.geometry(pc)
    assert [(g.layers, g.paged) for g in geo.groups] == [(10, True),
                                                         (30, False)]
    w = FAM.weights.make_weights(cfg, SEED, "float32")
    rng = np.random.default_rng(4)
    reqs = [serving.Request(rng.integers(0, 256, n).astype(np.int32),
                            max_new_tokens=6, do_sample=False)
            for n in (70, 11)]
    res = _engine(w, config=pc, num_slots=2).run(reqs)
    assert _served_gaps(reqs, res, cfg).max() <= TOL


def test_shares_of_the_held_experts_add_up_to_the_whole_layer(weights):
    """Four shares of two experts and no shared expert give what the uncut
    reference gives for the whole layer: the layer is models/moe.py's, the
    one xing4 and afmoe run, here with the published 1e-6 under the chosen
    scores' sum."""
    assert L.moe_ffn is MOE.moe_ffn is A.moe_ffn is X.moe_ffn
    p = jax.tree_util.tree_map(lambda a: a[1], weights["moe"])
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 9, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, stats = MOE.moe_ffn(p, x, PC, shared=False)
        parts = [MOE.moe_ffn(p, x, PC, held=(lo, lo + 2), shared=False)
                 for lo in range(0, 8, 2)]
        p32 = {k: v for k, v in p.items() if not k.startswith("experts_")}
        xn = FAM.reference.rms(x, CFG["norm_eps"], p["ffn_norm_g"])
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


@pytest.mark.parametrize("config,eps", [
    (PC, 1e-6), (A.AfmoeConfig(), 1e-20), (X.Xing4Config(), 1e-20)],
    ids=["lfm2", "afmoe", "xing4"])
def test_the_routers_epsilon_is_the_models_own(config, eps):
    """Scores so small that the epsilon shows: the chosen weights are the
    scores over (their sum + the configuration's epsilon)."""
    assert config.route_norm_eps == eps
    c = config
    xn = jnp.zeros((1, 4), jnp.float32)
    router_w = jnp.zeros((4, c.n_routed_experts), jnp.float32)
    bias = -jnp.arange(c.n_routed_experts, dtype=jnp.float32)
    _, w = MOE.moe_route(xn, router_w, bias, c)
    k = c.num_experts_per_tok
    want = 0.5 / (0.5 * k + eps) * c.routed_scaling_factor
    np.testing.assert_allclose(np.asarray(w), np.full((1, k), want),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# the cache manager: pages beside a state


def test_snapshot_carries_the_state_and_resumes_bitwise(weights, ids):
    """A snapshot mid-decode holds the state group's array beside K and V
    under the geometry's names; a fresh engine resumes from it with the
    tokens the first goes on to serve, and traces nothing."""
    profiler.reset_serving_counters()
    eng = _engine(weights, num_slots=3).warm_up()      # 3 slots: fresh shapes
    warm = profiler.serving_counters()["paged_traces"]
    assert warm == 3 + 1                               # rungs 8, 16, 32; [3,1]
    assert not np.asarray(eng._pools[2]).any()         # warm-up moved nothing
    r = serving.Request(ids[0, :120], max_new_tokens=20, do_sample=False)
    eng.submit(r)
    for _ in range(10):
        eng.step()
    state = eng.state_dict()
    assert state["u"].shape == (7, 3, KEEP, H) and state["u"].any()
    assert state["k"].shape == (2, 3 * 20 + 1, PAGE, 2, 16)
    assert "group_pools" not in state
    rest = eng.run()[r.request_id].tokens
    other = _engine(weights, num_slots=3)
    other.load_state_dict(state)
    resumed = other.run()
    assert list(resumed.values())[0].tokens == rest
    assert profiler.serving_counters()["paged_traces"] == warm
    bal = other.pool.balance()
    assert bal["conserved"] and bal["refcounts_accounted"]


def test_drain_releases_the_pages_and_a_replay_starts_the_state_anew(
        weights, ids):
    eng = _engine(weights, num_slots=2)
    r = serving.Request(ids[0, :70], max_new_tokens=20, do_sample=False)
    eng.submit(r)
    for _ in range(4):
        eng.step()
    assert eng.pool.pages_in_use > 0
    assert eng.drain() == [r]
    assert eng.pool.pages_in_use == 0
    # the drained slot's state is still on the device; a replay of the
    # request on another engine's slot 0, or this one's, starts from zero
    res = _engine(weights, num_slots=2).run([r])
    assert _served_gaps([r], res).max() <= TOL


def test_a_first_group_that_is_not_paged_is_refused(weights):
    conv_only = FAM.sut.program_config(dict(
        CFG, num_hidden_layers=2, layer_types=["conv", "conv"]))
    w = FAM.weights.make_weights(dict(
        CFG, num_hidden_layers=2, layer_types=["conv", "conv"]), SEED,
        "float32")
    with pytest.raises(ValueError, match="first cache group must be a paged"):
        _engine(w, config=conv_only)


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
    assert f"does not serve the lfm2 model with {option!r} yet" in str(e.value)


def test_decode_kernel_is_refused_with_its_reason(caplog):
    import logging
    with caplog.at_level(logging.INFO, logger="paddle_tpu.lfm2"):
        assert PC.served_model.kernel_ok(PC, 1, 16) is False
    assert "2 KV heads under 4 query heads" in caplog.text


# ---------------------------------------------------------------------------
# the step the engine builds


def _step_jaxpr(eng, b, t, named=False):
    """The jaxpr of the step the engine dispatches at [b, t], on the idle
    operands warm_up sends (Engine._step_args); ``named``: of the step over
    named operands inside it (``_make_paged_step``'s ``fn``)."""
    args, kw = eng._step_args(b, t, named=named)
    step = eng._paged_step.named if named else eng._paged_step
    return jax.make_jaxpr(functools.partial(step, **kw))(*args)


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def test_pools_and_state_are_the_layer_scans_carry(weights):
    """As for GPT, xing4 and afmoe: on the jaxpr of the step the engine
    builds, no scan takes a pool or the state as xs or returns it as ys;
    each of the two scans (the leading dense conv layer; the two periods)
    carries the state, the second K and V too, and the step returns all
    three first."""
    eng = _engine(weights, num_slots=7)
    shapes = [a.shape for a in eng._pools]
    for b, t in ((7, 1), (1, CHUNK)):
        closed = _step_jaxpr(eng, b, t)
        found = list(_scans(closed.jaxpr))
        assert len(found) == 2                          # layer_plan's segments
        for eqn in found:
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            xs = [v.aval.shape for v in eqn.invars[nc + nk:]]
            ys = [v.aval.shape for v in eqn.outvars[nk:]]
            assert not [s for s in xs + ys if s in shapes], (xs, ys)
            carried = [v.aval.shape for v in eqn.outvars[:nk]]
            assert shapes[2] in carried
        assert shapes[0] in [v.aval.shape for v in found[1].outvars]
        assert [v.aval.shape for v in closed.jaxpr.outvars[:3]] == shapes


def test_scopes_are_in_the_lowered_steps_op_names(weights):
    eng = _engine(weights, num_slots=7)
    for b, t in ((7, 1), (1, CHUNK)):
        args, kw = eng._step_args(b, t)
        text = eng._paged_step.lower(*args, **kw).as_text(debug_info=True)
        for scope in ("pt_conv_in", "pt_conv_mix", "pt_conv_out",
                      "pt_attn_gqa", "pt_moe_route", "pt_moe_experts"):
            assert scope in text, scope


def test_engine_step_holds_no_branch_on_a_models_name():
    import inspect
    src = "".join(inspect.getsource(f) for f in (
        E.Engine.step, E.Engine._step, E.Engine._iterate_paged,
        E.Engine._prefill_chunk, E.Engine._try_reserve, E.Engine._admit,
        E.Engine._free_slot, E.Engine._count_bound_cache,
        E.Engine._table_arg))
    for word in ("lfm2", "afmoe", "xing", "gpt", "GPT", "_model.name"):
        assert word not in src


# the step of each model the engine served before this one, traced at a toy
# size and hashed. GPT's was renewed when its engine came to keep the qkv
# stack transposed: the same step equation for equation but the qkv
# product's, which reads the layer's weight [3H, H] and contracts its
# second axis. xing4's and afmoe's were renewed when the expert layer came
# to multiply each token by the experts it chose (pairs sorted by expert,
# grouped products over the stacks read whole at the layer's index) where
# the dense form multiplied every token by every held expert. lfm2's
# own step was recorded on the commit before the seam's groups took a type
# of their own (de6c695): the second model of state groups, the Jamba
# family, left it as it was. A change that means to alter one of these
# steps replaces its digest.
PARENT_STEPS = {
    "gpt":
        "772e4481636a560663ac7898d82b14ebf86f334ada5402aacef270ac8bd6d3c8",
    "xing4":
        "3dfd2bd2f00f323cb60ebf6624081665c50c7348c36b8655b1474642c81518ec",
    "afmoe":
        "ecdb2ef3c792f7659522323b30cda479caa11a05d77f1a0f42a0ddff074fac4b",
    "lfm2":
        "5866cd26322a822f4f19ad6601d3b8fee57ba9463b21ee53b3b70a2ebd592ade",
}


def _toy_engine(model):
    if model == "gpt":
        from paddle_tpu.models.gpt import GPTConfig
        from paddle_tpu.models.gpt_hybrid import init_gpt_params
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=64)
        params = init_gpt_params(cfg, jax.random.key(0))
    elif model == "xing4":
        cfg = X.Xing4Config(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=3,
            first_k_dense_replace=1, n_routed_experts=4,
            num_experts_per_tok=2, num_attention_heads=2, q_lora_rank=8,
            kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, hc_mult=2)
        params = X.init_xing4_params(cfg, jax.random.key(1))
    elif model == "lfm2":
        cfg = L.Lfm2Config(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=5,
            num_dense_layers=1, num_experts=4, num_experts_per_tok=2,
            num_attention_heads=4, num_key_value_heads=2,
            layer_types=(L.CONV, L.FULL, L.CONV, L.CONV, L.CONV))
        params = L.init_lfm2_params(cfg, jax.random.key(3))
    else:
        cfg = A.AfmoeConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=5,
            num_dense_layers=1, num_experts=4, num_experts_per_tok=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            sliding_window=16)
        params = A.init_afmoe_params(cfg, jax.random.key(2))
    return serving.Engine(params=params, config=cfg, num_slots=2,
                          page_size=8, prefill_chunk=16, max_seq_len=64)


@pytest.mark.parametrize("model", sorted(PARENT_STEPS))
def test_other_models_steps_are_the_parents_jaxpr_for_jaxpr(model):
    eng = _toy_engine(model)
    text = "\n".join(str(_step_jaxpr(eng, b, t, named=True))
                     for b, t in ((2, 1), (1, 16)))
    assert "0x" not in text                 # nothing of this process in it
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STEPS[model]
    (group,) = eng._geo.paged[:1]
    assert group.paged
    if model == "lfm2":
        return
    assert all(g.paged for g in eng._geo.groups)
    profiler.reset_serving_counters()
    eng.run([serving.Request(np.arange(1, 20), max_new_tokens=3)])
    c = profiler.serving_counters()
    assert c["state_slots_bound"] == c["cache_bytes_all_paged"] == 0
