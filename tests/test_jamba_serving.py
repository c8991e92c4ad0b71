"""Jamba (models/jamba.py) against its family's plain reference
(benchmark/families/jamba/reference.py: float32, no cache, no state carried
from anywhere, a sequential scan over the row, imports nothing of the
program), and through ``serving.Engine`` on its normal path: Mamba-1
selective-scan layers whose two states a slot (the convolution's last three
input rows, the float32 recurrent state ``[16, d_inner]``) live beside the
paged K and V of a multi-query attention layer. CPU, float32, seeded random
weights, a toy width with the published layer pattern: 14 layers, attention
at 7 of period 14, d_state 16, d_conv 4, 4 query heads on 1 KV head of 16
(benchmark/tests/rehearsal/configs/tiny-jamba.json). Pages of 8, chunks of
up to 32: a prompt of 126 crosses three chunk boundaries and its last chunk
is padded. And the two selective-scan kernels in Pallas interpret mode
against the recurrence they replace.

Tolerance, on float32 logits of magnitude about 4 and spread 1: program and
reference do the same arithmetic in other orders (a state carried across
dispatches against one scan over the row, einsums against loops over
heads), so they differ by float32 summation order alone: 4e-5 absolute
holds twice the widest seen, and a state that is not zeroed, that pads
advance, or that moves under an idle slot shifts logits by 1e-2 and
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

import paddle_tpu as paddle  # noqa: E402
from benchmark.harness import loader, reference as R  # noqa: E402
from paddle_tpu import profiler, serving  # noqa: E402
from paddle_tpu.models import jamba as J  # noqa: E402
from paddle_tpu.ops.pallas_kernels import selective_scan as S  # noqa: E402

TOL = 4e-5
SEED = 2 ** 31 + 7
FAM = loader.load_family("jamba")
with open(os.path.join(ROOT, "benchmark", "tests", "rehearsal", "configs",
                       "tiny-jamba.json")) as _f:
    CFG = json.load(_f)
PC = FAM.sut.program_config(CFG)
PAGE, CHUNK, MAXSEQ = 8, 32, 160
DI, N, KEEP = 2 * CFG["hidden_size"], CFG["mamba_d_state"], 3
MAMBA = 13


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
    """K and V pools and both states (filled with ``fill``: what a recycled
    page, or a slot's last occupant, left), a page table whose pages are in
    no order, and the slots' numbers."""
    paged, conv, ssm = PC.served_model.geometry(PC).groups
    mp = MAXSEQ // PAGE
    pools = tuple(jnp.full(paged.pool_shape(slots * mp + 1, PAGE), fill,
                           jnp.float32) for _ in paged.names) \
        + (jnp.full(conv.state_shape(slots), fill, jnp.float32),
           jnp.full(ssm.state_shape(slots), fill, jnp.float32))
    table = np.arange(slots * mp, 0, -1, dtype=np.int32).reshape(slots, mp)
    s = jnp.arange(slots, dtype=jnp.int32)
    return pools, (jnp.asarray(table), s, s)


def test_programs_own_tree_has_the_familys_layout(weights):
    """``init_jamba_params`` and the benchmark family's ``make_weights``
    agree on every leaf's name and shape: one layout contract, stated twice
    because neither side may import the other."""
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
    own = jax.eval_shape(lambda k: J.init_jamba_params(PC, k),
                         jax.random.key(0))
    assert shapes(own) == shapes(weights)


def test_published_configuration_by_its_own_keys():
    """The benchmark's configuration file resolves to the published widths,
    whole: head_dim 128 from hidden / heads, attention at 7 and 21, the
    cache's three groups with the recurrent state in float32 beside
    bfloat16 pages and rows."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "jamba2-3B.json")) as f:
        pc = FAM.sut.program_config(json.load(f))
    assert (pc.head_dim, pc.d_inner, pc.num_hidden_layers) == (128, 5120, 28)
    assert J.runs(pc) == [(True, 7), (False, 1), (True, 13), (False, 1),
                          (True, 6)]
    geo = pc.served_model.geometry(pc)
    assert geo.dtype == "bfloat16"
    assert [(g.names, g.layers, g.row, g.paged, g.dtype)
            for g in geo.groups] == [
        (("k", "v"), 2, (128,), True, None),
        (("conv",), 26, (3 * 5120,), False, None),
        (("ssm",), 26, (16, 5120), False, "float32")]
    # the pool and both states in whole lanes, as they are stored
    assert geo.groups[0].pool_shape(131073, 16) == (2, 131073, 16, 128)
    assert geo.groups[2].state_shape(128) == (26, 128, 16, 5120)


def test_forward_matches_the_reference(weights, ids, ref_logits):
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, i: J.forward(p, PC, i))(weights, ids)
    np.testing.assert_allclose(np.asarray(got), ref_logits, atol=TOL, rtol=0)


@pytest.mark.parametrize("chunk", [PAGE, CHUNK], ids=["page_chunks",
                                                      "ladder_wide_chunks"])
@pytest.mark.parametrize("plen,total", [(9, 14), (37, 44), (126, 144)],
                         ids=["inside_one_chunk", "across_a_boundary",
                              "across_three_boundaries"])
def test_chunks_then_decode_through_pages_and_states_match_the_full_forward(
        weights, ids, ref_logits, chunk, plen, total):
    """One slot's prompt goes through the paged forward in chunks of
    ``chunk`` (the last one padded past ``valid``), then token by token to
    ``total``. Both states start as what the slot's last occupant left (1e3
    everywhere): the step makes them zero where ``start`` is 0, carries
    them from one chunk to the next, and a padded chunk leaves them where
    its last REAL position did. Every logit row equals the reference's row
    of its one full forward; the recurrent state stays float32."""
    pools, tables = _pools_and_tables(1, fill=1e3)
    step = jax.jit(lambda p, i, pl, s, v: J.paged_forward(
        p, PC, i, pl, s, v, tables, PAGE))
    row = ids[0]
    with jax.default_matmul_precision("highest"):
        for start in range(0, plen, chunk):
            valid = min(chunk, plen - start)
            win = np.zeros((1, chunk), np.int32)
            win[0, :valid] = row[start:start + valid]
            logits, pools, _ = step(weights, win, pools, jnp.asarray([start]),
                                    jnp.asarray([valid]))
            for a in pools[2:]:
                assert np.abs(np.asarray(a)).max() < 100  # no 1e3 left
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   ref_logits[0, plen - 1], atol=TOL, rtol=0)
        for pos in range(plen, total):
            logits, pools, _ = step(weights, row[None, pos:pos + 1], pools,
                                    jnp.asarray([pos]), jnp.asarray([1]))
            np.testing.assert_allclose(np.asarray(logits[0]),
                                       ref_logits[0, pos], atol=TOL, rtol=0)
    # K and V rows one head of 16 in whole lanes; the states a row a slot
    assert pools[0].shape == (1, MAXSEQ // PAGE + 1, PAGE, 128)
    assert pools[2].shape == (MAMBA, 1, KEEP * DI)
    assert pools[3].shape == (MAMBA, 1, N, DI)


def test_the_recurrent_state_stays_float32_beside_a_narrower_type(weights):
    """In an engine whose compute type is bfloat16 the pages and the
    convolution rows are bfloat16 and the recurrent state float32, from
    allocation through dispatches, snapshots and restores."""
    cfg = J.JambaConfig.from_dict(CFG, compute_dtype="bfloat16")
    w = FAM.weights.make_weights(CFG, SEED, "bfloat16")
    eng = _engine(w, config=cfg, num_slots=2)
    assert [a.dtype for a in eng._pools] == [jnp.bfloat16] * 3 + [jnp.float32]
    r = serving.Request(np.arange(1, 40, dtype=np.int32), max_new_tokens=8,
                        do_sample=False)
    eng.submit(r)
    for _ in range(4):
        eng.step()
    assert [a.dtype for a in eng._pools] == [jnp.bfloat16] * 3 + [jnp.float32]
    state = eng.state_dict()
    assert state["ssm"].dtype == np.float32 and state["ssm"].any()
    assert state["conv"].dtype == np.uint8            # bfloat16's raw bytes
    rest = eng.run()[r.request_id].tokens
    other = _engine(w, config=cfg, num_slots=2)
    other.load_state_dict(state)
    assert [a.dtype for a in other._pools] == \
        [jnp.bfloat16] * 3 + [jnp.float32]
    np.testing.assert_array_equal(np.asarray(other._pools[3]),
                                  state["ssm"])
    assert list(other.run().values())[0].tokens == rest


def test_a_slot_that_a_dispatch_does_not_advance_keeps_its_state(
        weights, ids, ref_logits):
    """Three slots prefilled to 5, 50 and 121 positions and a fourth half
    way through its prompt (``start`` 0 in a decode dispatch, as the engine
    passes a prefilling slot's position), then eight decode steps of all
    four in one [4, 1] dispatch with the fourth inert (``valid`` 0): both
    its states are bit for bit what its chunk left, and its prompt's second
    chunk afterwards ends on the reference's logits."""
    pools, tables = _pools_and_tables(4)
    step = jax.jit(lambda p, i, pl, s, v, t: J.paged_forward(
        p, PC, i, pl, s, v, t, PAGE))
    one = lambda b: tuple(t[b:b + 1] if t.ndim == 1 else t[b:b + 1]
                          for t in tables)
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
        held = [np.array(a[:, 3]) for a in pools[2:]]
        assert all(np.abs(h).max() > 0 for h in held)
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
            for a, h in zip(pools[2:], held):
                assert (np.asarray(a[:, 3]) == h).all()
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


def test_engine_serves_through_pages_and_states(weights, ids):
    """Through submit / step / on_token with every flag at its default but
    the sizes: three requests of 20, 61 and 130 prompt tokens in one batch
    of 4 slots. Every served token is the reference's best at its position,
    the allocator balances, the kernels' counters hold the prompts' real
    positions and the decode dispatches' live slots, and admission counted
    what it bound: one attention layer's pages of K and V rows and 13
    Mamba layers' two states a slot, against fourteen layers' pages."""
    profiler.reset_serving_counters()
    eng = _engine(weights)
    assert eng.pool.prefix_cache_enabled is False       # resolved to off
    assert len(eng._group_pools) == 1 and len(eng._pools) == 4
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
    assert c["ssm_scan_positions"] == 130 + 20 + 61
    # every token after the first comes from a decode dispatch
    assert c["ssm_step_slots"] == 13 + 29 + 8
    pages = 18 + 7 + 9                          # 144, 50 and 70 positions
    page_bytes = PAGE * 2 * 16 * 4
    assert c["state_slots_bound"] == 3
    assert c["cache_bytes_bound"] == pages * page_bytes \
        + 3 * MAMBA * (KEEP * DI + N * DI) * 4
    assert c["cache_bytes_all_paged"] == 14 * pages * page_bytes


def test_a_reused_slot_starts_from_zero_and_recompute_is_bitwise(weights,
                                                                 ids):
    """One slot: a long request, then a short one in the slot it left (both
    states start from zero, by the step alone: the host resets nothing),
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
        assert np.abs(np.asarray(eng._pools[3])).max() > 0
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


@pytest.mark.parametrize("kwargs,option", [
    ({"speculate_k": 2}, "spec"),
    ({"quant": "int8"}, "quant"),
    ({"adapter_slots": 2}, "adapters"),
    ({"mp": 2}, "mp"),
    ({"role": "prefill"}, "kv_transfer"),
    ({"prefix_cache": True}, "prefix_cache"),
])
def test_what_is_not_supported_raises_one_sentence(weights, kwargs, option):
    with pytest.raises(ValueError) as e:
        _engine(weights, **kwargs)
    assert f"does not serve the jamba model with {option!r} yet" in \
        str(e.value)


# ---------------------------------------------------------------------------
# the kernels


def _scan_case(B, T, D=256, L=3, slots=20, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return dict(
        state=f(L, slots, N, D), layer=jnp.int32(1),
        slots=jnp.asarray(rng.permutation(slots)[:B], jnp.int32),
        start=jnp.asarray([0] + [5] * (B - 1), jnp.int32),
        valid=jnp.asarray([T] * max(B - 1, 1) + [0] * (B > 1), jnp.int32),
        dt=jnp.asarray(rng.uniform(0, 0.1, (B * T, D)), jnp.float32),
        dtx=0.1 * f(B * T, D), A=-jnp.asarray(rng.uniform(1, 16, (N, D)),
                                              jnp.float32),
        Bt=f(B, N, T), Ct=f(B, N, T))


@pytest.mark.parametrize("kernel,B,T,channels", [
    (S.ssm_scan, 2, 40, 128), (S.ssm_scan, 1, 256, 128),
    (S.ssm_step, 3, 1, None), (S.ssm_step, 16, 1, 128)],
    ids=["scan_40_positions_in_128_channel_blocks",
         "scan_256_positions_in_groups_of_128", "step_3_slots",
         "step_16_slots_eight_rows_a_block"])
def test_kernels_in_interpret_mode_match_the_recurrence(kernel, B, T,
                                                        channels):
    """The Pallas kernels (interpret mode) against ``scan_reference``, the
    plain recurrence over positions: the same outputs and the same whole
    state after the update in place, a row whose ``start`` is 0 from zero,
    a row whose ``valid`` is 0 untouched, every other slot and layer
    untouched."""
    case = _scan_case(B, T)
    want_y, want_s = S.scan_reference(**case)
    got_y, got_s = kernel(**case, channels=channels, interpret=True)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-5, rtol=1e-5)
    touched = np.zeros(case["state"].shape[:2], bool)
    touched[1, np.asarray(case["slots"])[np.asarray(case["valid"]) > 0]] = 1
    same = np.asarray(got_s) == np.asarray(case["state"])
    assert same[~touched].all() and not same[touched].all()


def test_the_recurrence_is_the_papers_one_position_at_a_time():
    """``scan_reference`` over a row from zero is the equations of the
    module's docstring walked in plain numpy."""
    case = _scan_case(1, 7, D=8, L=2, slots=1)
    y, s = S.scan_reference(**case)
    dt, dtx, a = (np.asarray(case[k]) for k in ("dt", "dtx", "A"))
    bt, ct = np.asarray(case["Bt"])[0], np.asarray(case["Ct"])[0]
    st = np.zeros((N, 8))
    for t in range(7):
        st = np.exp(dt[t] * a) * st + dtx[t] * bt[:, t:t + 1]
        np.testing.assert_allclose(np.asarray(y)[t],
                                   (st * ct[:, t:t + 1]).sum(0), rtol=1e-5,
                                   atol=1e-6)
    np.testing.assert_allclose(np.asarray(s)[1, 0], st, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the step the engine builds


def _step_jaxpr(eng, b, t):
    args, kw = eng._step_args(b, t)
    return jax.make_jaxpr(functools.partial(eng._paged_step, **kw))(*args)


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def test_pools_and_states_are_the_walks_carry(weights):
    """As for the other models: on the jaxpr of the step the engine builds,
    no scan takes a pool or a state as xs or returns it as ys; the walk's
    two Mamba runs (seven layers, six) carry both states and its attention
    run the K and V pools (a scan carries what it changes), and the step
    returns all four first."""
    eng = _engine(weights, num_slots=7)
    shapes = [a.shape for a in eng._pools]
    for b, t in ((7, 1), (1, CHUNK)):
        closed = _step_jaxpr(eng, b, t)
        found = list(_scans(closed.jaxpr))
        carried = {}
        for eqn in found:
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            xs = [v.aval.shape for v in eqn.invars[nc + nk:]]
            ys = [v.aval.shape for v in eqn.outvars[nk:]]
            assert not [s for s in xs + ys if s in shapes], (xs, ys)
            mine = [v.aval.shape for v in eqn.outvars[:nk]]
            if any(s in mine for s in shapes):
                carried[len(carried)] = (eqn.params["length"], mine)
        assert [n for n, _ in carried.values()] == [7, 1, 6]
        for n, mine in carried.values():
            want = shapes[2:] if n != 1 else shapes[:2]
            assert all(s in mine for s in want), (n, mine)
        assert [v.aval.shape for v in closed.jaxpr.outvars[:4]] == shapes


def test_scopes_are_in_the_lowered_steps_op_names(weights):
    eng = _engine(weights, num_slots=7)
    for b, t in ((7, 1), (1, CHUNK)):
        args, kw = eng._step_args(b, t)
        text = eng._paged_step.lower(*args, **kw).as_text(debug_info=True)
        for scope in ("pt_ssm_in", "pt_ssm_scan", "pt_ssm_out",
                      "pt_attn_mqa", "pt_mlp", "pt_layers", "pt_head",
                      "pt_tail"):
            assert scope in text, scope


def test_decode_kernel_is_refused_with_its_reason(caplog):
    import logging
    with caplog.at_level(logging.INFO, logger="paddle_tpu.jamba"):
        assert PC.served_model.kernel_ok(PC, 1, 16) is False
    assert "1 KV head under 4 query heads on cpu" in caplog.text


def test_decode_kernel_is_taken_on_a_tpu(monkeypatch):
    """On a TPU the engine is told that the [B, 1] read sweeps the live
    pages (``paged_mqa_decode``), so ``decode_pages_swept`` counts those."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert PC.served_model.kernel_ok(PC, 1, 16) is True


def test_multi_query_decode_kernel_in_interpret_mode_matches_the_gather():
    """The [B, 1] read of the attention layers on a TPU (``paged_mqa_decode``
    over the pages each slot holds, interpret mode) against the gather read
    it replaces there: 20 query heads on one KV head of 128 lanes, pages of
    8 in no order, slots whose last position is the first of a page, the
    last of one, the table's very end and 0."""
    from paddle_tpu.ops.pallas_kernels.paged_mqa import paged_mqa_decode
    from paddle_tpu.serving.paged_attention import grouped_attend, \
        latent_window, window_mask
    rng = np.random.default_rng(1)
    L, P, ps, B, H, d, MP = 2, 200, 8, 5, 20, 128, 24
    pool = lambda: jnp.asarray(rng.standard_normal((L, P, ps, d)),
                               jnp.float32)
    kc, vc = pool(), pool()
    table = jnp.asarray(rng.permutation(P)[:B * MP].reshape(B, MP),
                        jnp.int32)
    pos = jnp.asarray([0, 7, 8, 150, MP * ps - 1], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    got = paged_mqa_decode(q, kc, vc, 1, table, pos, page_size=ps,
                           interpret=True)
    S = MP * ps
    window = lambda c: latent_window(c, 1, table, d).reshape(B, S, 1, d)
    with jax.default_matmul_precision("highest"):
        want = grouped_attend(q[:, None], window(kc), window(vc),
                              window_mask(pos[:, None], jnp.arange(S)[None]),
                              jnp.float32)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=0)
