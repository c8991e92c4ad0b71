"""Autoregressive generation for the GPT family (capability parity with the
reference ecosystem's `model.generate`, ref PaddleNLP-class usage of
python/paddle — greedy/top-k/top-p sampling over a KV cache).

TPU-native design: ONE jitted XLA program runs prefill + the whole decode
loop (`lax.scan` over positions, static shapes, preallocated KV cache with
`dynamic_update_slice`). The eager alternative — one dispatch per token —
would pay a host->device round trip per step; here the host sees a single
call per generation.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .gpt import ln_fp32


def _delta_proj(x, w, aid, ad_l, name):
    """Base projection ``x @ w`` plus the per-row LoRA delta when this
    layer's adapter slab covers ``name`` — the SAME ops (take + batched
    einsum pair + masked compose, ops/pallas_kernels/quant_gemm.py) the
    serving engine runs, so a solo reference decode with ``adapters=`` is
    bitwise comparable to the engine's mixed-adapter batch rows."""
    base = x @ w.astype(x.dtype)
    if ad_l is None or name not in ad_l:
        return base
    from ..ops.pallas_kernels.quant_gemm import lora_delta, compose_delta
    A_l, B_l = ad_l[name]
    return compose_delta(base, lora_delta(x, A_l, B_l, aid), aid)


def _layer_cached(p, h, kc, vc, start, nh, eps, aid=None, ad_l=None):
    """One transformer block over h [B,T,H] with KV cache [B,Smax,nh,d].
    Positions [start, start+T) are written; attention keys are the cache
    prefix up to start+T (mask below). Mirrors gpt_block_fn math
    (models/gpt.py) plus cache read/write. ``aid``/``ad_l`` (serving
    adapters reference path): per-row adapter ids + this layer's slab
    rows, joined into the out/up/down projections — qkv stays un-adapted
    by construction (serving/adapters.py)."""
    B, T, H = h.shape
    d = H // nh

    def ln(x, g, b):
        return ln_fp32(x, g, b, eps)

    h1 = ln(h, p["ln1_g"], p["ln1_b"])
    qkv = h1 @ p["qkv_w"].astype(h.dtype) + p["qkv_b"].astype(h.dtype)
    q, k, v = jnp.split(qkv.reshape(B, T, 3, nh, d), 3, axis=2)
    q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]
    kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype), (0, start, 0, 0))
    vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype), (0, start, 0, 0))
    Smax = kc.shape[1]
    # causal mask in absolute positions: query t attends keys <= start+t
    key_pos = jnp.arange(Smax)[None, :]
    q_pos = start + jnp.arange(T)[:, None]
    mask = key_pos <= q_pos                                   # [T, Smax]
    scores = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                        kc.astype(jnp.float32)) / (d ** 0.5)
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhts,bshd->bthd", probs,
                     vc.astype(jnp.float32)).astype(h.dtype)
    attn = _delta_proj(ctx.reshape(B, T, H), p["out_w"], aid, ad_l,
                       "out_w") + p["out_b"].astype(h.dtype)
    h = h + attn
    h2 = ln(h, p["ln2_g"], p["ln2_b"])
    up = _delta_proj(h2, p["up_w"], aid, ad_l, "up_w") + \
        p["up_b"].astype(h.dtype)
    up = jax.nn.gelu(up, approximate=True)
    return h + _delta_proj(up, p["down_w"], aid, ad_l, "down_w") + \
        p["down_b"].astype(h.dtype), kc, vc


def _final_ln(params, config, xlast):
    """Final LN (fp32) over last-position hidden states [B,H] — shared
    with the mp serving forward, which follows it with a vocab-SHARDED
    head matmul (serving/mp_forward.py) instead of the full one below."""
    xf = xlast.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    xn = (xf - mu) * jax.lax.rsqrt(var + config.layer_norm_epsilon)
    return xn * params["lnf_g"].astype(jnp.float32) + \
        params["lnf_b"].astype(jnp.float32)


def _final_logits(params, config, xlast):
    """Final LN (fp32) + LM head over last-position hidden states [B,H]."""
    return _final_ln(params, config, xlast) @ \
        params["head_w"].astype(jnp.float32)


def _forward_cached(params, config, ids, kc, vc, start, adapters=None):
    """ids [B,T] at absolute positions [start, start+T); returns logits of
    the LAST position [B,V] and the updated cache. ``adapters`` = (aid [B],
    slabs) — the solo-reference adapter path (slabs ride the layer scan,
    exactly like the paged engine's fused step)."""
    compute = jnp.dtype(config.compute_dtype or "float32")
    B, T = ids.shape
    pos = start + jnp.arange(T)
    x = params["wte"].astype(compute)[ids] + \
        jnp.take(params["wpe"].astype(compute), pos, axis=0)[None]
    nh = config.num_heads
    aid, slabs = adapters if adapters is not None else (None, None)

    def layer_fn(h, xs):
        if adapters is not None:
            xs, ad_l = xs[:-1], xs[-1]
        else:
            ad_l = None
        p_l, kc_l, vc_l = xs
        h, kc_l, vc_l = _layer_cached(p_l, h, kc_l, vc_l, start, nh,
                                      config.layer_norm_epsilon, aid, ad_l)
        return h, (kc_l, vc_l)

    xs = (params["blocks"], kc, vc)
    if adapters is not None:
        xs = xs + (slabs,)
    x, (kc, vc) = jax.lax.scan(layer_fn, x, xs)
    return _final_logits(params, config, x[:, -1]), kc, vc


def _mask_logits(logits, temperature, top_k, top_p, rows=None):
    """Sampling logits transform: temperature scale, static top-k cut,
    nucleus (top-p) cut. temperature/top_p are TRACED operands (scalar or
    per-row [B] — sweeping them never recompiles); top_k stays static (it
    changes the top_k kernel's shape). top_p=None skips the nucleus branch
    structurally (the old static `top_p in (None, 1.0)` contract).

    ``rows`` (traced bool, scalar or per-row [B]: the rows whose result
    is read, _next_token's sample_mask) gates the nucleus cut at run
    time: its sorts and gathers run only when some such row asks for
    top_p < 1.0. A traced 1.0 keeps every token, so skipping the cut
    then is bitwise the same."""
    t = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    if getattr(t, "ndim", 0) == logits.ndim - 1:
        t = t[..., None]
    logits = logits / t
    if top_k is not None and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is None:
        return logits
    p = jnp.asarray(top_p, jnp.float32)

    def nucleus(logits):
        pk = p[..., None] if p.ndim == logits.ndim - 1 else p
        sort_idx = jnp.argsort(-logits, axis=-1)
        sorted_logits = jnp.take_along_axis(logits, sort_idx, axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = (cum - probs) < pk         # always keeps the top token
        # p >= 1.0 must keep EVERY token (a traced 1.0 stands in for
        # "no nucleus cut" — the serving engine's per-slot top_p=None):
        # float32 cumsum saturates at 1.0 before the tail, so without this
        # the comparison would mask tiny-probability tail tokens and break
        # bitwise parity with the structural top_p=None skip.
        keep_sorted = keep_sorted | (pk >= 1.0)
        inv = jnp.argsort(sort_idx, axis=-1)
        keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
        return jnp.where(keep, logits, -jnp.inf)

    if rows is None:
        return nucleus(logits)
    return jax.lax.cond(jnp.any(rows & (p < 1.0)), nucleus,
                        lambda logits: logits, logits)


def _next_token(logits, subs, sample_mask, temperature, top_k, top_p):
    """The sampling tail of every serving executable: the next token of
    each row of logits [B, V] as [B] int32 — a categorical draw under
    ``subs`` (one key a row, or one key for all of logits) where
    ``sample_mask`` (traced bool, per-row [B] or scalar) is set, the
    argmax elsewhere. The temperature scale, the cuts and the Gumbel draw
    over [B, V] run only in a dispatch where some row samples, and the
    nucleus cut only where such a row asks for one (_mask_logits' rows):
    a greedy dispatch pays for the argmax alone. Both branches live in
    the one executable; tokens are bitwise those of
    ``where(sample_mask, categorical(subs, _mask_logits(...)), argmax)``."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    draw = jax.random.categorical if subs.ndim == 0 else \
        jax.vmap(jax.random.categorical)

    def sampled():
        masked = _mask_logits(logits, temperature, top_k, top_p,
                              rows=sample_mask)
        return jnp.where(sample_mask, draw(subs, masked).astype(jnp.int32),
                         greedy)

    return jax.lax.cond(jnp.any(sample_mask), sampled, lambda: greedy)


def _select_token(logits, key, do_sample, temperature, top_k, top_p):
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, _mask_logits(logits, temperature, top_k, top_p)).astype(jnp.int32)


def _is_stop(tok, stop_token_ids):
    """Elementwise membership of tok in the static stop-id tuple."""
    hit = tok == stop_token_ids[0]
    for s in stop_token_ids[1:]:
        hit = hit | (tok == s)
    return hit


def _verify_accept(logits, ids_next, nprop, emit, do_sample, temperature,
                   top_p, key_data, top_k):
    """Speculative accept scan over the verify window's logits
    [B, T, V] (T = k+1). Lane i's logits score the token AT window
    position i, so its selected token is the TRUE next token after i;
    the slot keeps emitting while each selected token matches the draft's
    proposal for the next lane (ids_next [B, T], garbage in the last
    lane — never compared, since lane T-1 has ``i == nprop`` at most).

    PRNG discipline — the whole bitwise contract lives here: each slot's
    threefry key splits ONCE PER EMITTED token, greedy included, exactly
    like the plain fused step and ``_generate_jit``. Lanes past the
    accept point (``going`` False) select garbage greedily, split
    nothing, and advance nothing, so a sampled stream replays
    ``generate_from_params`` token-for-token no matter where rejection
    lands. temperature/top_p are per-slot traced operands; top_k is
    static (shape of the top_k cut).

    Returns (toks [B, T] — lanes >= n_emit[b] garbage, n_emit [B] int32
    with emit=False slots at 0, new key_data [B, 2] uint32)."""
    B, T, _ = logits.shape

    def step(carry, xs):
        key_data, going, n_emit = carry
        lg, nxt_prop, i = xs
        pair = jax.vmap(jax.random.split)(
            jax.random.wrap_key_data(key_data))
        t = _next_token(lg, pair[:, 1], do_sample & going, temperature,
                        top_k, top_p)
        new_kd = jnp.where(going[:, None],
                           jax.random.key_data(pair[:, 0]), key_data)
        n_emit = n_emit + going
        going = going & (i < nprop) & (t == nxt_prop)
        return (new_kd, going, n_emit), t

    (key_data, _, n_emit), toks = jax.lax.scan(
        step, (key_data, emit, jnp.zeros((B,), jnp.int32)),
        (jnp.swapaxes(logits, 0, 1), ids_next.T, jnp.arange(T)))
    return toks.T, n_emit, key_data


def _cfg_view(cfg):
    """cfg is a hashable static tuple (nh, L, H, eps, compute_dtype_str) —
    GPTConfig itself is a mutable dataclass and cannot key the jit cache."""
    class config:  # minimal view the helpers read
        num_heads, num_layers, hidden_size, layer_norm_epsilon = cfg[:4]
        compute_dtype = cfg[4]
    return config


def _alloc_cache(config, rows, total):
    nh = config.num_heads
    d = config.hidden_size // nh
    compute = jnp.dtype(config.compute_dtype or "float32")
    shape = (config.num_layers, rows, total, nh, d)
    return jnp.zeros(shape, compute), jnp.zeros(shape, compute)


# number of times _generate_jit has actually been TRACED (the body runs
# only on a cache miss) — the no-recompile evidence for traced sampling
# params. Tests measure deltas across sampling-config sweeps.
_gen_traces = 0


@partial(jax.jit, static_argnames=("cfg", "max_new_tokens", "do_sample",
                                   "top_k", "stop_token_ids"))
def _generate_jit(params, ids, key, adapters=None, *, cfg, max_new_tokens,
                  do_sample, temperature, top_k, top_p, stop_token_ids):
    global _gen_traces
    _gen_traces += 1
    config = _cfg_view(cfg)
    B, P = ids.shape
    total = P + max_new_tokens
    kc, vc = _alloc_cache(config, B, total)

    logits, kc, vc = _forward_cached(params, config, ids, kc, vc, 0,
                                     adapters=adapters)
    key, sub = jax.random.split(key)
    tok = _select_token(logits, sub, do_sample, temperature, top_k, top_p)
    finished = jnp.zeros((B,), bool) if stop_token_ids is None else \
        _is_stop(tok, stop_token_ids)

    def step(carry, i):
        kc, vc, tok, finished, key = carry
        key, sub = jax.random.split(key)
        # tok was produced for absolute position P+i; feed it there
        logits, kc, vc = _forward_cached(params, config, tok[:, None],
                                         kc, vc, P + i, adapters=adapters)
        nxt = _select_token(logits, sub, do_sample, temperature, top_k, top_p)
        if stop_token_ids is not None:
            nxt = jnp.where(finished, stop_token_ids[0], nxt)
            finished = finished | _is_stop(nxt, stop_token_ids)
        return (kc, vc, nxt, finished, key), tok

    (kc, vc, last, finished, key), toks = jax.lax.scan(
        step, (kc, vc, tok, finished, key),
        jnp.arange(max_new_tokens - 1), length=max_new_tokens - 1)
    out = jnp.concatenate([toks.T, last[:, None]], axis=1)  # [B, new]
    return jnp.concatenate([ids, out], axis=1)


@partial(jax.jit, static_argnames=("cfg", "max_new_tokens", "num_beams",
                                   "eos_token_id"))
def _beam_search_jit(params, ids, *, cfg, max_new_tokens, num_beams,
                     length_penalty, eos_token_id):
    """Beam search in one XLA program (capability: the reference generate's
    beam_search mode). Beams live in the batch dim ([B*W, ...]); the KV
    cache is re-gathered along that dim on every beam reorder."""
    config = _cfg_view(cfg)
    B, P = ids.shape
    W = num_beams
    total = P + max_new_tokens
    NEG = jnp.float32(-1e9)

    # prefill ONCE per example ([B, P]), then fan the cache out to W beams
    # (the W beams are identical until the first expansion)
    kc1, vc1 = _alloc_cache(config, B, total)
    logits, kc1, vc1 = _forward_cached(params, config, ids, kc1, vc1, 0)
    kc = jnp.repeat(kc1, W, axis=1)
    vc = jnp.repeat(vc1, W, axis=1)
    first = jax.nn.log_softmax(logits, axis=-1)             # [B, V]
    V = first.shape[-1]
    scores, tok = jax.lax.top_k(first, W)                   # [B, W]
    tok = tok.astype(jnp.int32)
    finished = (tok == eos_token_id) if eos_token_id is not None else \
        jnp.zeros((B, W), bool)
    seqs = jnp.zeros((B, W, max_new_tokens), jnp.int32)
    seqs = seqs.at[:, :, 0].set(tok)

    def step(carry, i):
        kc, vc, tok, scores, finished, seqs = carry
        logits, kc, vc = _forward_cached(params, config,
                                         tok.reshape(B * W)[:, None],
                                         kc, vc, P + i)
        logp = jax.nn.log_softmax(logits, axis=-1).reshape(B, W, V)
        # finished beams extend only with eos at unchanged score
        if eos_token_id is not None:
            frozen = jnp.full((V,), NEG).at[eos_token_id].set(0.0)
            logp = jnp.where(finished[:, :, None], frozen[None, None], logp)
        cand = scores[:, :, None] + logp                    # [B, W, V]
        scores, idx = jax.lax.top_k(cand.reshape(B, W * V), W)
        beam = (idx // V).astype(jnp.int32)                 # [B, W]
        tok = (idx % V).astype(jnp.int32)
        # reorder beam state (incl. KV cache) along the B*W dim
        gidx = (jnp.arange(B)[:, None] * W + beam).reshape(B * W)
        kc = jnp.take(kc, gidx, axis=1)
        vc = jnp.take(vc, gidx, axis=1)
        seqs = jnp.take_along_axis(seqs, beam[:, :, None], axis=1)
        finished = jnp.take_along_axis(finished, beam, axis=1)
        if eos_token_id is not None:
            finished = finished | (tok == eos_token_id)
        seqs = seqs.at[:, :, i + 1].set(tok)
        return (kc, vc, tok, scores, finished, seqs), None

    (kc, vc, tok, scores, finished, seqs), _ = jax.lax.scan(
        step, (kc, vc, tok, scores, finished, seqs),
        jnp.arange(max_new_tokens - 1), length=max_new_tokens - 1)
    # pick the best beam under the GNMT length penalty
    if eos_token_id is not None:
        lengths = jnp.where(
            finished,
            1 + jnp.argmax((seqs == eos_token_id).astype(jnp.int32), axis=-1),
            max_new_tokens).astype(jnp.float32)
    else:
        lengths = jnp.full((B, W), float(max_new_tokens))
    norm = ((5.0 + lengths) / 6.0) ** length_penalty
    best = jnp.argmax(scores / norm, axis=-1)               # [B]
    best_seq = jnp.take_along_axis(
        seqs, best[:, None, None], axis=1)[:, 0]            # [B, new]
    return jnp.concatenate([ids, best_seq], axis=1)


def _normalize_stop(eos_token_id, stop_token_ids):
    """Merge the scalar eos alias with the stop-id list into one static
    tuple (eos first: it doubles as the pad id for finished rows, keeping
    the scalar form's output bitwise unchanged). Returns None when no stop
    condition was requested."""
    ids = []
    if eos_token_id is not None:
        ids.append(int(eos_token_id))
    if stop_token_ids is not None:
        if isinstance(stop_token_ids, (int, jnp.integer)):
            stop_token_ids = [stop_token_ids]
        for s in stop_token_ids:
            if int(s) not in ids:
                ids.append(int(s))
    return tuple(ids) if ids else None


def _collect_params(model):
    """GPTForCausalLM Layer -> the functional param layout
    (models/gpt_hybrid.py init_gpt_params)."""
    from .gpt import stack_block_params
    gpt = model.gpt
    head_w = (gpt.wte.weight._data.T if model.lm_head is None
              else model.lm_head.weight._data)
    return {
        "wte": gpt.wte.weight._data,
        "wpe": gpt.wpe.weight._data,
        "lnf_g": gpt.ln_f.weight._data,
        "lnf_b": gpt.ln_f.bias._data,
        "head_w": head_w,
        "blocks": stack_block_params(model),
    }


def _cfg_key(config):
    return (config.num_heads, config.num_layers, config.hidden_size,
            config.layer_norm_epsilon, config.compute_dtype)


def _logical_qkv(params, config):
    """Undo HybridTrainStep's head-major qkv storage (config.qkv_head_major,
    set under sequence parallelism — see tp_overlap.to_qkv_head_major).
    Decode always splits qkv as [3, nh, d], so head-major blocks must be
    permuted back to the logical layout or q/k/v columns interleave into
    the wrong heads. Pure relabeling, bitwise identical. Runs once per
    generate_from_params CALL (amortized over the whole decode); for
    repeated-generation loops pre-permute once — or use the serving
    Engine, which does this at construction. The engine's own tree
    (``_stored_qkv``) is logical already and only comes back to
    ``qkv_w``."""
    if "qkv_wt" in params["blocks"]:
        return _trained_qkv(params)
    if not getattr(config, "qkv_head_major", False):
        return params
    from ..distributed.tp_overlap import qkv_head_major_perm
    import numpy as np
    inv = np.argsort(qkv_head_major_perm(config.hidden_size,
                                         config.num_heads))
    blocks = dict(params["blocks"])
    blocks["qkv_w"] = jnp.asarray(blocks["qkv_w"])[..., inv]
    blocks["qkv_b"] = jnp.asarray(blocks["qkv_b"])[..., inv]
    return {**params, "blocks": blocks}


def _stored_qkv(params):
    """A logical tree as the serving engine keeps it: a full-precision qkv
    stack ``qkv_w`` [L, H, 3H] stored transposed as ``qkv_wt`` [L, 3H, H].
    Row-major, that is XLA:TPU's default layout for what the fused product
    reads (the contracted H minor-most), so the paged step reads a layer's
    slice as stored; read as ``qkv_w``, the slice is copied into that order
    in every layer of every dispatch. A quantized stack (its ``qkv_w_s``
    scale beside it) keeps its form."""
    blocks = params["blocks"]
    if "qkv_w" not in blocks or "qkv_w_s" in blocks:
        return params
    blocks = dict(blocks)
    blocks["qkv_wt"] = jnp.swapaxes(jnp.asarray(blocks.pop("qkv_w")), -1, -2)
    return {**params, "blocks": blocks}


def _trained_qkv(params):
    """``_stored_qkv`` undone: ``qkv_wt`` back to ``qkv_w``, exactly; a
    tree in any other form as it is."""
    blocks = params["blocks"]
    if "qkv_wt" not in blocks:
        return params
    blocks = dict(blocks)
    blocks["qkv_w"] = jnp.swapaxes(blocks.pop("qkv_wt"), -1, -2)
    return {**params, "blocks": blocks}


def _check_temperature(do_sample, temperature):
    """Sampled decoding divides logits by the temperature (_mask_logits);
    <= 0 would blow them up to +/-inf before the 1e-6 clamp makes the
    distribution a numerical accident. Greedy paths never read it."""
    if do_sample and temperature <= 0:
        raise ValueError(
            f"temperature must be > 0 when do_sample=True, got "
            f"{temperature} (use do_sample=False for greedy decoding)")


def generate_from_params(params, input_ids, config, max_new_tokens=32,
                         do_sample=False, temperature=1.0, top_k=None,
                         top_p=None, eos_token_id=None, seed=0,
                         stop_token_ids=None, adapters=None):
    """Generate from a FUNCTIONAL param tree (models/gpt_hybrid.py
    init_gpt_params layout) — the public decode entry for params produced
    by HybridTrainStep / the serving Engine, no Layer required.

    ``adapters=(adapter_id, slabs)`` is the solo-reference path for the
    adapter serving parity gates: slabs is an AdapterRegistry's
    ``device_slabs()`` dict and every row of this generation runs under
    ``adapter_id`` (0 = base) through the SAME take/einsum/compose ops
    the engine's mixed-adapter fused step uses — so engine rows are
    bitwise comparable against this for any batch composition."""
    from ..tensor_impl import Tensor
    ids = jnp.asarray(input_ids._data if isinstance(input_ids, Tensor)
                      else input_ids, jnp.int32)
    _check_temperature(do_sample, temperature)
    if max_new_tokens < 1:
        if max_new_tokens == 0:
            return Tensor(ids)
        raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
    assert ids.shape[1] + max_new_tokens <= config.max_seq_len, \
        "prompt + max_new_tokens exceeds config.max_seq_len (wpe table)"
    params = _logical_qkv(params, config)
    if adapters is not None:
        aid, slabs = adapters
        adapters = (jnp.full((ids.shape[0],), int(aid), jnp.int32),
                    {n: tuple(s) for n, s in slabs.items()})
    out = _generate_jit(params, ids, jax.random.key(seed), adapters,
                        cfg=_cfg_key(config),
                        max_new_tokens=int(max_new_tokens),
                        do_sample=bool(do_sample),
                        temperature=float(temperature),
                        top_k=None if top_k in (None, 0)
                        else min(int(top_k), config.vocab_size),
                        top_p=None if top_p in (None, 1.0) else float(top_p),
                        stop_token_ids=_normalize_stop(eos_token_id,
                                                       stop_token_ids))
    return Tensor(out)


def generate(model, input_ids, max_new_tokens=32, do_sample=False,
             temperature=1.0, top_k=None, top_p=None, eos_token_id=None,
             seed=0, num_beams=1, length_penalty=1.0, stop_token_ids=None):
    """Generate from a GPTForCausalLM Layer. Collects its weights into the
    functional layout (models/gpt_hybrid.py init_gpt_params) and runs the
    single-program decode above."""
    from ..tensor_impl import Tensor
    config = model.config
    ids = jnp.asarray(input_ids._data if isinstance(input_ids, Tensor)
                      else input_ids, jnp.int32)
    _check_temperature(do_sample, temperature)
    if max_new_tokens < 1:
        if max_new_tokens == 0:
            return Tensor(ids)
        raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
    assert ids.shape[1] + max_new_tokens <= config.max_seq_len, \
        "prompt + max_new_tokens exceeds config.max_seq_len (wpe table)"
    params = _collect_params(model)
    stop = _normalize_stop(eos_token_id, stop_token_ids)
    if num_beams > 1:
        if do_sample:
            raise ValueError("beam search is deterministic; do_sample=True "
                             "with num_beams > 1 is not supported")
        if stop is not None and len(stop) > 1:
            raise NotImplementedError(
                "beam search supports a single stop id (the frozen-beam "
                "rewrite needs one pad token); pass eos_token_id only")
        out = _beam_search_jit(params, ids, cfg=_cfg_key(config),
                               max_new_tokens=int(max_new_tokens),
                               num_beams=int(num_beams),
                               length_penalty=float(length_penalty),
                               eos_token_id=None if stop is None else stop[0])
        return Tensor(out)
    out = _generate_jit(params, ids, jax.random.key(seed), cfg=_cfg_key(config),
                        max_new_tokens=int(max_new_tokens),
                        do_sample=bool(do_sample),
                        temperature=float(temperature),
                        top_k=None if top_k in (None, 0)
                        else min(int(top_k), config.vocab_size),
                        top_p=None if top_p in (None, 1.0) else float(top_p),
                        stop_token_ids=stop)
    return Tensor(out)
