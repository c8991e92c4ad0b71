"""GPT autoregressive generation: jitted KV-cache decode vs naive
re-forward (ref capability: PaddleNLP-class model.generate)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.generation import (
    _mask_logits, _next_token, _verify_accept)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM


def _tiny_model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
                    max_seq_len=64, dropout=0.0, use_flash=False,
                    compute_dtype="float32", remat=False)
    return GPTForCausalLM(cfg), cfg


def test_greedy_matches_naive_loop():
    model, cfg = _tiny_model()
    model.eval()
    prompt = np.array([[3, 14, 15, 92], [6, 5, 35, 89]], np.int64)
    out = model.generate(paddle.to_tensor(prompt), max_new_tokens=6)
    got = np.asarray(out.numpy())
    assert got.shape == (2, 10)
    # naive: full re-forward each step, argmax of last position
    ids = prompt.copy()
    for _ in range(6):
        logits = model(paddle.to_tensor(ids)).numpy()
        nxt = logits[:, -1].argmax(-1)
        ids = np.concatenate([ids, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(got, ids)


def test_eos_freezes_sequence():
    model, cfg = _tiny_model()
    model.eval()
    prompt = np.array([[1, 2]], np.int64)
    ref = np.asarray(model.generate(paddle.to_tensor(prompt),
                                    max_new_tokens=8).numpy())[0]
    first = int(ref[2])  # first generated token is deterministic (greedy)
    out = np.asarray(model.generate(paddle.to_tensor(prompt),
                                    max_new_tokens=8,
                                    eos_token_id=first).numpy())[0]
    # once eos is produced every later token is eos
    assert (out[2:] == first).all()


def test_sampling_seeded_and_topk():
    model, cfg = _tiny_model()
    model.eval()
    prompt = np.array([[7, 8, 9]], np.int64)
    a = np.asarray(model.generate(paddle.to_tensor(prompt), max_new_tokens=5,
                                  do_sample=True, top_k=8, temperature=0.8,
                                  seed=11).numpy())
    b = np.asarray(model.generate(paddle.to_tensor(prompt), max_new_tokens=5,
                                  do_sample=True, top_k=8, temperature=0.8,
                                  seed=11).numpy())
    np.testing.assert_array_equal(a, b)
    assert a.shape == (1, 8)
    # max_new_tokens=0 returns the prompt unchanged
    z = np.asarray(model.generate(paddle.to_tensor(prompt),
                                  max_new_tokens=0).numpy())
    np.testing.assert_array_equal(z, prompt)
    # top_k beyond vocab is clamped, not a crash
    w = model.generate(paddle.to_tensor(prompt), max_new_tokens=2,
                       do_sample=True, top_k=10_000, seed=3)
    assert np.asarray(w.numpy()).shape == (1, 5)


def test_top_p_masks_tail():
    from paddle_tpu.models.generation import _select_token
    logits = jnp.log(jnp.asarray([[0.6, 0.25, 0.1, 0.05]]))
    # top_p=0.5: only the 0.6 token survives -> sampling is deterministic
    for s in range(5):
        tok = _select_token(logits, jax.random.key(s), True, 1.0, None, 0.5)
        assert int(tok[0]) == 0


def test_beam1_matches_greedy():
    model, cfg = _tiny_model()
    model.eval()
    prompt = np.array([[3, 14, 15, 92]], np.int64)
    greedy = np.asarray(model.generate(paddle.to_tensor(prompt),
                                       max_new_tokens=6).numpy())
    beam1 = np.asarray(model.generate(paddle.to_tensor(prompt),
                                      max_new_tokens=6,
                                      num_beams=1).numpy())
    np.testing.assert_array_equal(greedy, beam1)


def test_beam_score_not_worse_than_greedy():
    model, cfg = _tiny_model()
    model.eval()
    prompt = np.array([[5, 6], [40, 2]], np.int64)

    def seq_logprob(full):
        """Sum of next-token logprobs for the generated suffix."""
        logits = model(paddle.to_tensor(full.astype(np.int64))).numpy()
        lp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), axis=-1)
        s = 0.0
        for b in range(full.shape[0]):
            for t in range(prompt.shape[1], full.shape[1]):
                s += float(lp[b, t - 1, full[b, t]])
        return s

    g = np.asarray(model.generate(paddle.to_tensor(prompt),
                                  max_new_tokens=5).numpy())
    bm = np.asarray(model.generate(paddle.to_tensor(prompt),
                                   max_new_tokens=5, num_beams=4,
                                   length_penalty=0.0).numpy())
    assert bm.shape == g.shape
    assert seq_logprob(bm) >= seq_logprob(g) - 1e-4


def test_beam_rejects_sampling():
    model, cfg = _tiny_model()
    prompt = np.array([[1]], np.int64)
    with pytest.raises(ValueError):
        model.generate(paddle.to_tensor(prompt), max_new_tokens=2,
                       num_beams=2, do_sample=True)


# ---------------------------------------------------------------------------
# the serving executables' sampling tail (generation._next_token): the draw
# and the cuts run behind predicates on the traced operands, and every token
# stays bitwise what the ungated expression gives

_B, _V = 6, 211
# rows whose result is the draw: do_sample & emit of the paged step
_MASKS = {
    "all-greedy": ([0] * 6, [1] * 6),
    "all-sampled": ([1] * 6, [1] * 6),
    "mixed-rows": ([1, 0, 0, 1, 0, 1], [1] * 6),
    # rows 1 and 4 ask to sample and do not emit: they read the argmax
    "emit-false-rows": ([1, 1, 0, 0, 1, 0], [1, 0, 1, 1, 0, 1]),
    "sampling-rows-none-emit": ([1, 0, 1, 0, 0, 1], [0, 1, 0, 1, 1, 0]),
}
_TOP_P = {
    "none": None,                                   # structural skip
    "ones": [1.0] * 6,                              # traced stand-in for None
    "cut": [0.8] * 6,
    # rows 0 and 3 sample under "mixed-rows": one with a cut, one without
    "mixed": [0.8, 0.5, 1.0, 1.0, 0.9, 0.3],
    # the only rows with a cut (1, 2, 4) never sample under "mixed-rows":
    # the nucleus branch is skipped while the draw runs
    "cut-on-greedy-rows": [1.0, 0.6, 0.7, 1.0, 0.5, 1.0],
}


def _tail_operands(seed=0):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(0, 3, (_B, _V)), jnp.float32)
    subs = jax.random.split(jax.random.key(seed + 11), _B)
    temp = jnp.asarray(rng.uniform(0.4, 1.6, _B), jnp.float32)
    return logits, subs, temp


def _ungated(logits, subs, mask, temperature, top_k, top_p):
    """The tail as every builder spelled it before the helper."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    draw = jax.random.categorical if subs.ndim == 0 else \
        jax.vmap(jax.random.categorical)
    sampled = draw(subs, _mask_logits(logits, temperature, top_k, top_p)
                   ).astype(jnp.int32)
    return jnp.where(mask, sampled, greedy)


@pytest.mark.parametrize("top_k", [None, 7], ids=["no-top-k", "top-k-7"])
@pytest.mark.parametrize("top_p", sorted(_TOP_P))
@pytest.mark.parametrize("rows", sorted(_MASKS))
def test_next_token_is_bitwise_the_ungated_tail(rows, top_p, top_k):
    logits, subs, temp = _tail_operands()
    do_sample, emit = (jnp.asarray(m, bool) for m in _MASKS[rows])
    p = _TOP_P[top_p]
    p = None if p is None else jnp.asarray(p, jnp.float32)
    run = lambda fn: np.asarray(jax.jit(
        lambda lg, s, ds, em, t, pp: fn(lg, s, ds & em, t, top_k, pp)
    )(logits, subs, do_sample, emit, temp, p))
    got, want = run(_next_token), run(_ungated)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int32 and got.shape == (_B,)
    greedy = np.asarray(jnp.argmax(logits, -1))
    quiet = ~(np.asarray(do_sample) & np.asarray(emit))
    np.testing.assert_array_equal(got[quiet], greedy[quiet])


@pytest.mark.parametrize("do_sample", [False, True])
@pytest.mark.parametrize("top_p", [1.0, 0.8])
def test_next_token_one_key_for_the_batch(do_sample, top_p):
    """``_select_token``'s call (``generate_from_params``): one key for the
    batch, scalar operands."""
    logits, subs, temp = _tail_operands(3)
    args = (logits[:1], subs[0], jnp.asarray(do_sample), temp[0])
    run = lambda fn: np.asarray(jax.jit(
        lambda lg, s, m, t, p: fn(lg, s, m, t, None, p))(
            *args, jnp.float32(top_p)))
    np.testing.assert_array_equal(run(_next_token), run(_ungated))


def test_next_token_keeps_the_sorts_and_the_draw_in_branches(primitives):
    """Structure of the helper itself: argmax outside, the draw under one
    cond, the nucleus cut's sorts under a second one inside it; no nucleus
    code at all with a structural top_p=None."""
    logits, subs, temp = _tail_operands()
    mask = jnp.zeros(_B, bool)
    for top_p, sorts in ((jnp.ones(_B), True), (None, False)):
        closed = jax.make_jaxpr(
            lambda lg, s, m, t, p: _next_token(lg, s, m, t, None, p))(
                logits, subs, mask, temp, top_p)
        found = list(primitives(closed.jaxpr))
        assert ("argmax", False) in found
        assert ("random_bits", True) in found
        assert ("random_bits", False) not in found
        assert (("sort", True) in found) == sorts
        assert ("sort", False) not in found
        depth = [n for n, _ in found].count("cond")
        assert depth == (2 if sorts else 1)


@pytest.mark.parametrize("rows", ["all-greedy", "mixed-rows", "all-sampled"])
def test_verify_accept_lanes_are_bitwise_the_ungated_tail(rows):
    """The accept scan of speculative verify draws through the helper once a
    lane: tokens, emitted counts and keys equal a scan over the ungated
    expression, whatever the mix of greedy and sampled slots."""
    T = 4
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(0, 3, (_B, T, _V)), jnp.float32)
    do_sample = jnp.asarray(_MASKS[rows][0], bool)
    emit = jnp.asarray([1, 1, 0, 1, 1, 1], bool)
    temp = jnp.asarray(rng.uniform(0.5, 1.5, _B), jnp.float32)
    top_p = jnp.asarray(_TOP_P["mixed"], jnp.float32)
    kd = jax.random.key_data(jax.random.split(jax.random.key(9), _B))
    nprop = jnp.asarray([3, 0, 2, 1, 3, 2], jnp.int32)

    def reference(ids_next):
        def step(carry, xs):
            kd, going, n = carry
            lg, prop, i = xs
            pair = jax.vmap(jax.random.split)(jax.random.wrap_key_data(kd))
            t = _ungated(lg, pair[:, 1], do_sample & going, temp, None,
                         top_p)
            kd = jnp.where(going[:, None], jax.random.key_data(pair[:, 0]),
                           kd)
            n = n + going
            going = going & (i < nprop) & (t == prop)
            return (kd, going, n), t
        (kd_, _, n), toks = jax.lax.scan(
            step, (kd, emit, jnp.zeros(_B, jnp.int32)),
            (jnp.swapaxes(logits, 0, 1), ids_next.T, jnp.arange(T)))
        return toks.T, n, kd_

    # proposals that agree with the target for a while, so lanes past 0 run
    first = jax.jit(reference)(jnp.zeros((_B, T), jnp.int32))[0]
    ids_next = jnp.asarray(np.asarray(first)).at[:, 2].add(1) % _V
    want = jax.jit(reference)(ids_next)
    got = jax.jit(lambda x: _verify_accept(
        logits, x, nprop, emit, do_sample, temp, top_p, kd, None))(ids_next)
    assert int(np.asarray(want[1]).max()) > 1
    for g, w, what in zip(got, want, ("tokens", "n_emit", "keys")):
        g, w = np.asarray(g), np.asarray(w)
        if what == "tokens":        # lanes past n_emit are garbage
            live = np.arange(T)[None, :] < np.asarray(want[1])[:, None]
            g, w = g[live], w[live]
        np.testing.assert_array_equal(g, w, err_msg=what)
