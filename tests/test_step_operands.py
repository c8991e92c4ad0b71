"""The slot operands of a paged dispatch as one buffer (serving/operands.py):
what the host packs the jitted step unpacks bit for bit, whatever the shape,
the cache groups and the riders; the engine sends one array a dispatch and
fetches one (two more uploads beside it with a quantised pool's scale
tables), counted where they are sent; and the step fed this way serves the
tokens of ``generate_from_params``, greedy and sampled rows in one batch.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu import profiler, serving
from paddle_tpu.models.generation import generate_from_params
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.gpt_hybrid import init_gpt_params
from paddle_tpu.serving.operands import (
    Operands, StepLayout, pack_out, split_out,
)

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


# ---------------------------------------------------------------------------
# the layout

# float32 values whose bit patterns must survive the ride as int32: 1.0 (the
# idle value), a denormal, the largest finite, a negative zero
_FLOATS = np.array([1.0, 1e-45, 3.4028235e38, -0.0, 0.7, 2.5],
                   np.float32)
# uint32 key words with the top bit set (negative as int32)
_WORDS = np.array([0xFFFFFFFF, 0x80000000, 0x7FFFFFFF, 0, 0xDEADBEEF, 1],
                  np.uint64).astype(np.uint32)


def _values(layout, rng):
    """A value a field of ``layout``, at the field's shape and dtype."""
    out = {}
    for name, (_, shape, dtype) in layout.fields.items():
        n = int(np.prod(shape))
        if dtype == np.bool_:
            v = rng.integers(0, 2, n).astype(bool)
        elif dtype == np.float32:
            v = np.resize(rng.permutation(_FLOATS), n)
        elif dtype == np.uint32:
            v = np.resize(rng.permutation(_WORDS), n)
        else:
            v = rng.integers(-2 ** 31, 2 ** 31, n).astype(np.int32)
        out[name] = v.reshape(shape)
    return out


_GROUPS = {
    "one-group": ((12,), False),
    "two-groups": ((20, 6), False),                 # full + window ring
    "state-group": ((64, 0), False),                # paged + slot numbers
    "adapters": ((12,), True),
    "two-groups-adapters": ((8, 0), True),
}


@pytest.mark.parametrize("shape", [(16, 1), (64, 1), (1, 16), (1, 512)],
                         ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("groups", sorted(_GROUPS))
def test_pack_then_unpack_under_jit_is_bit_for_bit(shape, groups):
    widths, adapters = _GROUPS[groups]
    layout = StepLayout(*shape, widths, adapters)
    B, T = shape
    assert layout.size == B * (T + 3 + sum(w or 1 for w in widths)
                               + 5 + int(adapters))
    # every word of the buffer belongs to exactly one field
    cover = np.zeros(layout.size, int)
    for f in layout.fields.values():
        cover[f.offset:f.offset + f.size] += 1
    assert (cover == 1).all()

    buf = np.full(layout.size, -1, np.int32)
    views = layout.views(buf)
    want = _values(layout, np.random.default_rng(B * 1000 + T))
    layout.pack(views, **want)
    got = jax.jit(layout.unpack)(jnp.asarray(buf.copy()))
    assert isinstance(got, Operands)
    tables = got.table if len(widths) > 1 else (got.table,)
    assert len(tables) == len(widths)
    named = dict(got._asdict(), **dict(zip(layout.tables, tables)))
    del named["table"]
    if not adapters:
        assert named.pop("adapter_ids") is None
    assert named.keys() == want.keys()
    for name, value in want.items():
        field = layout.fields[name]
        out = np.asarray(named[name])
        assert out.dtype == field.dtype and out.shape == field.shape, name
        # bit for bit: compared as raw words, so -0.0 and a denormal count
        assert out.tobytes() == np.ascontiguousarray(value).tobytes(), name
    # a state group's field is the slots' numbers [B], a paged group's the
    # table [B, width]
    for name, w in zip(layout.tables, widths):
        assert layout.fields[name].shape == ((B, w) if w else (B,))


def test_layout_keys_the_step_by_what_fixes_it():
    a = StepLayout(16, 1, (128,))
    assert a == StepLayout(16, 1, (128,)) and hash(a) == hash(
        StepLayout(16, 1, (128,)))
    assert len({a, StepLayout(1, 16, (128,)), StepLayout(16, 1, (64,)),
                StepLayout(16, 1, (128,), True),
                StepLayout(16, 1, (128, 0))}) == 5
    with pytest.raises(AssertionError):
        a.pack(a.views(np.zeros(a.size, np.int32)), ids=0)  # fields missing


@pytest.mark.parametrize("anomaly", [False, True], ids=["plain", "guard"])
@pytest.mark.parametrize("stats", [False, True], ids=["nostats", "stats"])
@pytest.mark.parametrize("B", [1, 16])
def test_outputs_leave_as_one_vector_and_split_bit_for_bit(B, anomaly,
                                                           stats):
    rng = np.random.default_rng(B)
    nxt = rng.integers(0, 200_000, B).astype(np.int32)
    keys = np.resize(_WORDS, 2 * B).reshape(B, 2)
    ok = rng.integers(0, 2, B).astype(bool)
    st = np.array([7, -3, 2 ** 31 - 1], np.int32)
    out = jax.jit(lambda *a: pack_out(*a))(
        nxt, keys, ok if anomaly else None, st if stats else None)
    assert out.dtype == jnp.int32
    assert out.shape == (3 * B + B * anomaly + 3 * stats,)
    n, k, o, s = split_out(np.asarray(out), B, anomaly)
    assert n.tolist() == nxt.tolist()
    assert k.dtype == np.uint32 and k.tolist() == keys.tolist()
    assert (o is None) if not anomaly else o.tolist() == ok.tolist()
    assert (s is None) if not stats else s.tolist() == st.tolist()


def test_statistics_of_another_dtype_are_refused():
    with pytest.raises(TypeError, match="int32"):
        pack_out(jnp.zeros(2, jnp.int32), jnp.zeros((2, 2), jnp.uint32),
                 stats=jnp.zeros(3, jnp.float32))


# ---------------------------------------------------------------------------
# the engine's dispatches


def _requests(n, rng, **kw):
    return [serving.Request(rng.integers(0, CFG.vocab_size, plen),
                            max_new_tokens=m, **kw)
            for plen, m in ((3, 4), (13, 6), (21, 5), (37, 4))[:n]]


@pytest.mark.parametrize("quant,uploads", [(None, 1), ("int8", 3)],
                         ids=["plain", "quantised-pool"])
def test_a_dispatch_sends_one_buffer_and_fetches_one(quant, uploads):
    """Chunk and decode dispatches alike: one upload and one fetch a paged
    step; a quantised pool's two scale tables stay separate operands."""
    eng = _engine(quant=quant)
    profiler.reset_serving_counters()
    eng.run(_requests(4, np.random.default_rng(0)))
    c = profiler.serving_counters()
    assert c["paged_steps"] > c["chunk_steps"] > 4
    assert c["paged_uploads"] == uploads * c["paged_steps"]
    assert c["paged_fetches"] == c["paged_steps"]
    # warm_up's idle dispatches send their buffers too and fetch nothing
    eng.warm_up()
    w = profiler.serving_counters()
    assert w["paged_uploads"] - c["paged_uploads"] == uploads * 2
    assert w["paged_fetches"] == c["paged_fetches"]
    assert w["paged_steps"] == c["paged_steps"]


def test_the_buffer_of_a_shape_is_kept_not_made_again():
    eng = _engine()
    eng.run(_requests(2, np.random.default_rng(1)))
    kept = {k: v[1] for k, v in eng._operand_bufs.items()}
    assert sorted(kept) == [(1, 8), (3, 1)]
    eng.run(_requests(3, np.random.default_rng(2)))
    assert {k: v[1] for k, v in eng._operand_bufs.items()}.keys() == \
        kept.keys()
    for k, (layout, buf, _) in eng._operand_bufs.items():
        assert buf is kept[k] and buf.shape == (layout.size,)
        assert layout.table_widths == (eng.pool.table.shape[1],)


def test_packed_step_serves_generates_tokens_greedy_and_sampled_mixed():
    """One batch of greedy and sampled rows, swept temperatures and
    nucleus cuts, chunked prompts: each request's tokens are those of
    ``generate_from_params`` alone, so temperature, top_p and the keys
    reached the step bit for bit."""
    rng = np.random.default_rng(5)
    reqs = []
    for i in range(7):
        reqs.append(serving.Request(
            rng.integers(0, CFG.vocab_size, int(rng.integers(3, 30))),
            max_new_tokens=6, do_sample=bool(i % 2),
            temperature=0.5 + 0.3 * i, top_p=(0.7 + 0.04 * i, 1.0)[i % 3 == 0],
            seed=2 ** 31 + i))                   # a key word's top bit set
    served = _engine().run(reqs)
    for r in reqs:
        kw = {}
        if r.do_sample:
            kw = dict(do_sample=True, temperature=r.temperature,
                      top_p=r.top_p, seed=r.seed)
        want = np.asarray(generate_from_params(
            _params(), np.asarray(r.prompt)[None], CFG,
            max_new_tokens=r.max_new_tokens, **kw)._data)[0, len(r.prompt):]
        assert served[r.request_id].tokens == want.tolist()


def test_named_step_is_the_step_over_separate_operands():
    """``step.named`` takes what the packed step unpacks, one array an
    operand, and returns the outputs one by one: on the same idle operands
    the two agree."""
    eng = _engine()
    args, kw = eng._step_args(3, 1)
    packed = eng._paged_step(*args, **kw)
    named_args, none = eng._step_args(3, 1, named=True)
    assert none == {} and len(named_args) == 3 + 9
    *pools, nxt, keys = eng._paged_step.named(*named_args)
    n, k, ok, stats = split_out(np.asarray(packed[-1]), 3, False)
    assert ok is None and stats is None
    assert n.tolist() == np.asarray(nxt).tolist()
    assert k.tolist() == np.asarray(keys).tolist()
    for a, b in zip(pools, packed[:-1]):
        assert a.shape == b.shape and a.dtype == b.dtype
