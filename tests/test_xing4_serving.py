"""Xing4.0 (models/xing4.py) against its family's plain reference
(benchmark/families/xing4/reference.py: float32, no cache, imports nothing of
the program), and through ``serving.Engine`` on its normal path: a latent
(MLA) paged cache, sigmoid-routed experts beside a shared expert, the
four-stream mHC residual, the MTP module. CPU, float32, seeded random
weights, a toy width with every kind of layer: 1 dense + 2 expert layers, 8
experts top-2, 4 streams (benchmark/tests/rehearsal/configs/tiny-xing4.json).

Tolerances, all on float32 logits of magnitude about 0.5: program and
reference do the same arithmetic in other orders (one einsum against a loop
over experts, absorbed against expanded attention, a scan against a walk), so
they differ by float32 summation order alone: 2e-5 absolute holds a hundred
times that, and a wrong rotary pairing, a dropped shared expert or a missing
mHC map moves logits by 3e-3 and more."""
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
from paddle_tpu.models import moe as MOE, xing4 as X  # noqa: E402
from paddle_tpu.serving import engine as E  # noqa: E402
from paddle_tpu.serving.paged_attention import pool_head_dim  # noqa: E402

TOL = 2e-5
SEED = 2 ** 31 + 5
FAM = loader.load_family("xing4")
with open(os.path.join(ROOT, "benchmark", "tests", "rehearsal", "configs",
                       "tiny-xing4.json")) as _f:
    CFG = json.load(_f)
PC = FAM.sut.program_config(CFG)
PAGE, CHUNK, MAXSEQ = 8, 32, 128


@pytest.fixture(scope="module")
def weights():
    return FAM.weights.make_weights(CFG, SEED, "float32", mtp=True)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, CFG["vocab_size"],
                                             (2, 44)).astype(np.int32)


@pytest.fixture(scope="module")
def ref_logits(ids):
    return np.asarray(FAM.reference.served_logits(
        CFG, SEED, jnp.asarray(ids), "float32", R.mm_exact))


def _engine(weights, **kw):
    args = dict(num_slots=4, max_seq_len=MAXSEQ, page_size=PAGE,
                prefill_chunk=CHUNK)
    args.update(kw)
    tree = {k: v for k, v in weights.items() if k != "mtp"}
    return serving.Engine(params=tree, config=PC, **args)


def test_programs_own_tree_has_the_familys_layout(weights):
    """``init_xing4_params`` (the program's seeded tree) and the benchmark
    family's ``make_weights`` agree on every leaf's name and shape: one
    layout contract, stated twice because neither side may import the
    other."""
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
    own = jax.eval_shape(lambda k: X.init_xing4_params(PC, k, mtp=True),
                         jax.random.key(0))
    assert shapes(own) == shapes(weights)


def test_forward_matches_the_reference(weights, ids, ref_logits):
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, i: X.forward(p, PC, i))(weights, ids)
    np.testing.assert_allclose(np.asarray(got), ref_logits, atol=TOL, rtol=0)


def test_mtp_logits_match_the_reference(weights, ids):
    want = FAM.reference.mtp_logits(CFG, SEED, jnp.asarray(ids), "float32",
                                    R.mm_exact)
    with jax.default_matmul_precision("highest"):
        _, hidden = jax.jit(lambda p, i: X.forward(
            p, PC, i, return_hidden=True))(weights, ids)
        got = jax.jit(lambda p, h, i: X.mtp_logits(p, PC, h, i))(
            weights, hidden, ids)
    assert got.shape == (2, ids.shape[1] - 1, CFG["vocab_size"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL,
                               rtol=0)


def test_chunks_down_the_ladder_then_decode_match_the_full_forward(
        weights, ids, ref_logits):
    """One slot's prompt of 37 tokens goes through the paged forward in the
    engine's rungs (32, then the tail in a padded 8), then seven tokens one
    at a time: positions 37..43 cross the page boundary at 40. Every logit
    row the step returns equals the reference's row of the full forward."""
    (geo,) = PC.served_model.geometry(PC).groups
    pools = (jnp.zeros(geo.pool_shape(12, PAGE), jnp.float32),)
    table = jnp.asarray([[3, 5, 1, 7, 9, 2, 0, 0]], jnp.int32)
    step = jax.jit(lambda p, i, pl, s, v: X.paged_forward(
        p, PC, i, pl, s, v, table, PAGE))
    row, plen = ids[1], 37
    with jax.default_matmul_precision("highest"):
        for start, valid, width in ((0, 32, 32), (32, 5, 8)):
            win = np.zeros((1, width), np.int32)
            win[0, :valid] = row[start:start + valid]
            logits, pools, _ = step(weights, win, pools,
                                    jnp.asarray([start]), jnp.asarray([valid]))
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   ref_logits[1, plen - 1], atol=TOL, rtol=0)
        for pos in range(plen, 44):
            logits, pools, _ = step(weights, row[None, pos:pos + 1], pools,
                                    jnp.asarray([pos]), jnp.asarray([1]))
            np.testing.assert_allclose(np.asarray(logits[0]),
                                       ref_logits[1, pos], atol=TOL, rtol=0)
    # the cache holds one row a token a layer: c_kv normed and k_rope
    # rotated, 24 values here in whole lanes, and nothing in the trash page
    # but what padding lanes wrote
    assert pools[0].shape == (3, 12, PAGE, pool_head_dim(PC.latent_row))
    assert not np.asarray(pools[0])[..., PC.latent_row:].any()


def test_engine_serves_with_prefix_hit_and_cow_split(weights):
    """Through submit / step / on_token with every flag at its default but
    the sizes: a prompt, then a page-aligned sibling (prefix-cache hit) and
    an exact duplicate (its last, partial page is split copy-on-write). Every
    served token is the reference's best at its position, to the
    tolerance."""
    profiler.reset_serving_counters()
    eng = _engine(weights)
    assert eng.pool.num_pages > 0
    rng = np.random.default_rng(1)
    base = rng.integers(0, 256, 37).astype(np.int32)
    sib = np.concatenate([base[:24], rng.integers(0, 256, 9)]).astype(np.int32)
    streamed = []
    r1 = serving.Request(base, max_new_tokens=12, do_sample=False,
                         on_token=lambda _r, t: streamed.append(int(t)))
    res1 = eng.run([r1])[r1.request_id]
    assert streamed == res1.tokens
    r2 = serving.Request(sib, max_new_tokens=12, do_sample=False)
    r3 = serving.Request(base.copy(), max_new_tokens=12, do_sample=False)
    res = eng.run([r2, r3])
    assert res[r3.request_id].tokens == res1.tokens
    c = profiler.serving_counters()
    assert c["prefix_hits"] >= 2 and c["cow_copies"] >= 1
    bal = eng.pool.balance()
    assert bal["conserved"] and bal["refcounts_accounted"]

    rows = [(base, res1.tokens), (sib, res[r2.request_id].tokens)]
    seqs = np.zeros((2, 64), np.int32)
    for i, (p, toks) in enumerate(rows):
        seq = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
        seqs[i, :len(seq)] = seq
    ref = np.asarray(FAM.reference.served_logits(
        CFG, SEED, jnp.asarray(seqs), "float32", R.mm_exact))
    for i, (p, toks) in enumerate(rows):
        lg = ref[i, len(p) - 1:len(p) - 1 + len(toks)]
        gap = lg.max(-1) - lg[np.arange(len(toks)), toks]
        assert gap.max() <= TOL, (i, gap)


def test_absorbed_attention_equals_the_expanded_form(weights):
    """Decode reads the latent rows absorbed (q_nope Wkvb_K against c_kv, the
    value half of Wkvb after the weighted sum); the plain form expands K and
    V of every head first. Same context, to summation order."""
    p = jax.tree_util.tree_map(lambda a: a[0], weights["moe"])
    rng = np.random.default_rng(2)
    B, T, S = 2, 3, 21
    nh, nope, rope = PC.num_attention_heads, PC.qk_nope_head_dim, \
        PC.qk_rope_head_dim
    q_nope = jnp.asarray(rng.standard_normal((B, T, nh, nope)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((B, T, nh, rope)), jnp.float32)
    latent = jnp.asarray(rng.standard_normal((B, S, PC.latent_row)),
                         jnp.float32)
    mask = jnp.arange(S)[None, None, :] <= (S - T + jnp.arange(T))[None, :,
                                                                   None]
    mask = jnp.broadcast_to(mask, (B, T, S))
    with jax.default_matmul_precision("highest"):
        a = X.mla_attend_absorbed(p, q_nope, q_rope, latent, mask, PC)
        b = X.mla_attend_expanded(p, q_nope, q_rope, latent, mask, PC)
    assert a.shape == (B, T, nh * PC.v_head_dim)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=TOL, rtol=0)


def test_shares_of_the_held_experts_add_up_to_the_whole_layer(weights):
    """What expert parallelism asks of the layer: told which routed experts
    it holds, it routes over all of them and computes the held ones' part.
    Four shares of two experts, with the shared expert (which every chip
    computes alike) counted once, give what the uncut reference gives for
    the whole layer."""
    p = jax.tree_util.tree_map(lambda a: a[1], weights["moe"])
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 9, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, stats = X.moe_ffn(p, x, PC)
        parts = [X.moe_ffn(p, x, PC, held=(lo, lo + 2), shared=lo == 0)
                 for lo in range(0, 8, 2)]
        p32 = {k: (v if not k.startswith("experts_") else None)
               for k, v in p.items()}
        want = jnp.stack([FAM.reference.moe(
            p32, x[b], CFG, R.mm_exact,
            lambda e: {k: p[k][e] for k in FAM.weights.EXPERT_LEAVES})
            for b in range(2)])
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), atol=TOL,
                               rtol=0)
    np.testing.assert_allclose(np.asarray(sum(y for y, _ in parts)),
                               np.asarray(want), atol=TOL, rtol=0)
    # every token's two assignments land in exactly one share
    assert int(stats[0]) == 2 * 9 * 2 == sum(int(s[0]) for _, s in parts)
    assert int(stats[1]) == sum(int(s[1]) for _, s in parts)


def test_sinkhorn_gives_rows_and_columns_that_sum_to_one():
    rng = np.random.default_rng(4)
    # entries as the maps' logits have them (x~P of std 2.4 here and at the
    # published width): twenty rounds bring the rows, normalised before the
    # columns in each round, to 1 within 1e-3
    logits = jnp.asarray(rng.uniform(-3, 3, (5, 7, 4, 4)), jnp.float32)
    m = np.asarray(X.sinkhorn(logits, 20, 1e-6))
    assert (m >= 0).all()
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-3)
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-5)  # columns go last
    # and the three maps of a sublayer are what the reference makes of them
    w = FAM.weights.make_weights(CFG, SEED, "float32")
    p = jax.tree_util.tree_map(lambda a: a[0], w["dense"])
    Xs = jnp.asarray(rng.standard_normal((1, 6, 4, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        pre, post, res = X.mhc_maps(p, Xs, PC, "attn")
        want = FAM.reference.hyper_connect(
            p, Xs[0], "attn", lambda u: jnp.zeros_like(u), CFG, R.mm_exact)
    assert pre.shape == (1, 6, 4) and post.shape == (1, 6, 4)
    np.testing.assert_allclose(
        np.asarray(jnp.einsum("btij,btjh->btih", res, Xs)[0]),
        np.asarray(want), atol=TOL, rtol=0)


@pytest.mark.parametrize("kwargs,option", [
    ({"speculate_k": 2}, "spec"),
    ({"quant": "int8"}, "quant"),
    ({"adapter_slots": 2}, "adapters"),
    ({"mp": 2}, "mp"),
    ({"role": "prefill"}, "kv_transfer"),
    ({"role": "decode"}, "kv_transfer"),
])
def test_what_is_not_supported_raises_one_sentence(weights, kwargs, option):
    with pytest.raises(ValueError) as e:
        _engine(weights, **kwargs)
    assert f"does not serve the xing4 model with {option!r} yet" in str(e.value)
    if option == "kv_transfer":
        with pytest.raises(ValueError, match="kv_transfer"):
            _engine(weights).offer_transfer(None)


def test_counters_count_what_a_hand_made_routing_says(weights):
    """A router made by hand: token value v goes to experts v % 8 and
    (v + 1) % 8 in both expert layers. Three slots decode one token each
    (5, 5, 6) beside an empty slot: 6 assignments an expert layer, experts
    {5, 6, 7} touched, expert 6 the fullest with 3."""
    tree = {k: v for k, v in weights.items() if k != "mtp"}
    moe = dict(tree["moe"])
    moe["router_w"] = jnp.zeros_like(moe["router_w"])
    moe["router_bias"] = jnp.zeros_like(moe["router_bias"])
    tree["moe"] = moe
    (geo,) = PC.served_model.geometry(PC).groups
    pools = (jnp.zeros(geo.pool_shape(6, PAGE), jnp.float32),)
    table = jnp.asarray([[1], [2], [3], [4]], jnp.int32)
    toks = np.array([[5], [5], [6], [0]], np.int32)

    def routed(xn32, router_w, router_bias, config):
        n = xn32.shape[0]
        tok = jnp.asarray(toks[:, 0])[:n]
        idx = jnp.stack([tok % 8, (tok + 1) % 8], axis=-1)
        return idx, jnp.ones((n, 2), jnp.float32)

    orig = MOE.moe_route
    MOE.moe_route = routed
    try:
        _, _, stats = X.paged_forward(
            tree, PC, toks, pools, jnp.zeros(4, jnp.int32),
            jnp.asarray([1, 1, 1, 0]), table, PAGE)
    finally:
        MOE.moe_route = orig
    assert [int(s) for s in stats] == [2 * 6, 2 * 3, 3]

    # and the engine's ledger takes them, by kind of dispatch
    profiler.reset_serving_counters()
    eng = _engine(weights)
    r = serving.Request(np.arange(1, 20), max_new_tokens=4, do_sample=False)
    eng.run([r])
    c = profiler.serving_counters()
    assert c["chunk_steps"] == 2 and c["paged_steps"] == 5
    assert c["moe_layer_dispatches_chunk"] == 2 * 2
    assert c["moe_layer_dispatches_decode"] == 3 * 2
    assert c["moe_assignments_chunk"] == 19 * 2 * 2
    assert c["moe_assignments_decode"] == 3 * 2 * 2
    assert c["moe_touched_decode"] == 3 * 2 * 2     # one token: two experts
    assert 1 <= c["moe_load_max"] <= 16


def test_geometry_warm_up_and_snapshot(weights):
    """The pool's info reports the latent geometry; ``warm_up`` compiles
    every rung and the decode step, so traffic adds no trace; a snapshot
    carries the one latent array and resumes bitwise."""
    profiler.reset_serving_counters()
    eng = _engine(weights, num_slots=3).warm_up()    # 3 slots: fresh shapes
    warm = profiler.serving_counters()["paged_traces"]
    assert warm == 3 + 1                              # rungs 8, 16, 32; [3,1]
    (geo,) = eng._geo.groups                          # one group, no window
    assert geo.names == ("latent",) and geo.row == (PC.latent_row,)
    assert geo.window is None and eng._group_pools == [eng.pool]
    assert len(eng._pools) == 1
    assert eng._pools[0].shape == (3, eng.pool.num_pages, PAGE, 128)
    assert eng.kv_bytes_per_token() == 3 * 128 * 4
    prompt = np.arange(3, 50)
    r = serving.Request(prompt, max_new_tokens=10, do_sample=False)
    eng.submit(r)
    for _ in range(5):
        eng.step()
    state = eng.state_dict()
    assert state["latent"].shape[-1] == PC.latent_row and "kc" not in state
    rest = eng.run()[r.request_id].tokens
    other = _engine(weights, num_slots=3)
    other.load_state_dict(state)
    resumed = other.run()
    assert list(resumed.values())[0].tokens == rest
    assert profiler.serving_counters()["paged_traces"] == warm


def _step_jaxprs(eng):
    """The jaxprs of the step the engine dispatches, on the idle operands
    warm_up sends (Engine._step_args), at its two steady-state shapes
    [B, 1] and [1, chunk]."""
    out = []
    for b, t in ((eng.num_slots, 1), (1, CHUNK)):
        args, kw = eng._step_args(b, t)
        out.append(jax.make_jaxpr(
            functools.partial(eng._paged_step, **kw))(*args))
    return out


def test_latent_pool_is_the_layer_scans_carry(weights):
    """As for GPT (test_paged_serving.py): on the jaxpr of the step the
    engine builds, no scan takes the pool as xs or returns it as ys."""
    eng = _engine(weights, num_slots=7)
    shape = eng._pools[0].shape

    def scans(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scans(sub)

    for closed in _step_jaxprs(eng):
        found = list(scans(closed.jaxpr))
        assert len(found) == 2                      # dense layers, expert layers
        for eqn in found:
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            xs = [v.aval.shape for v in eqn.invars[nc + nk:]]
            ys = [v.aval.shape for v in eqn.outvars[nk:]]
            assert not [s for s in xs + ys if s[1:] == shape[1:]], (xs, ys)
            assert shape in [v.aval.shape for v in eqn.outvars[:nk]]
        assert closed.jaxpr.outvars[0].aval.shape == shape


def test_sampling_tail_lies_in_cond_branches(weights, primitives):
    """As for GPT: the tail is the engine's, so this model's step too keeps
    every sort and every draw of random bits under a cond, and the argmax
    and the per-slot key split outside (the router's top-k is no sort)."""
    for closed in _step_jaxprs(_engine(weights, num_slots=7)):
        found = set(primitives(closed.jaxpr))
        assert {("sort", True), ("random_bits", True),
                ("argmax", False), ("random_split", False)} <= found
        assert not {("sort", False), ("random_bits", False)} & found


def test_engine_step_holds_no_branch_on_a_models_name():
    import inspect
    src = inspect.getsource(E.Engine.step) + inspect.getsource(E.Engine._step) \
        + inspect.getsource(E.Engine._iterate_paged) \
        + inspect.getsource(E.Engine._prefill_chunk)
    for word in ("xing", "gpt", "GPT", "_model.name"):
        assert word not in src
