#!/usr/bin/env python
"""chip_smoke.py — the main path, end to end, on one TPU chip.

The quickest proof that the system still starts on the chip: one process,
no arguments, no subprocesses, the entry points a user calls
(``HybridTrainStep``, ``serving.Engine``, ``generate_from_params``), GPT-3
1.3B at full width and depth with seeded random weights. Legs, one line
``LEG <name> ok|FAIL ...`` each, all deciding the exit code:

  device     a TPU is the default backend; versions; compile-cache directory
  train      bf16 params, bf16 AdamW moments, remat, flash: 1 warm-up + 8
             steps on one seeded batch; loss starts at the init entropy, is
             finite and ends lower; the flash kernel is IN the compiled step; one
             compilation; block_until_ready and device_get time alike
  serve      the trained params handed to the paged engine: eight greedy
             requests; the decode kernel is in the [B,1] executable; two
             executables, no retrace, no page leak; first tokens are the
             training forward's arg-max; kernel on vs kernel off agree
  kernels    every single-chip Pallas kernel through Mosaic at the 1.3B
             shapes against its own reference
  four_chip  (>= 4 devices) the same trainer on dp2 x mp2, Engine(mp=4),
             ``__graft_entry__.dryrun_multichip(4)``, per-device balance

It measures nothing: step time and peak HBM are printed as smoke
information, never as a benchmark result. Without a TPU it exits non-zero
before running anything. The last stdout line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

The leg bodies take a config so tests/test_chip_smoke.py drives the same
code at toy width on the CPU (Pallas kernels interpreted); ``__main__`` has
no such mode.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import json
import math
import os
import sys
import time
import traceback

import numpy as np

# how a Pallas kernel compiled by Mosaic shows in a lowered module's text
MOSAIC_CALL = "tpu_custom_call"
MODEL = "gpt3-1.3B"
BATCH, SEQ = 8, 2048
PROMPT_LENS = (32, 77, 200, 200, 333, 333, 512, 512)
MAX_NEW = 32
# kernel-on vs kernel-off engines agree on at least this share of the
# generated tokens (position by position). The two decode reads differ in
# the last fp32 bits, the weights are random, so near-ties flip and a flip
# changes the rest of that request; a broken kernel agrees on ~1/vocab.
MIN_KERNEL_AGREEMENT = 0.5
# bf16 kernels vs their fp32-accumulating references, as max|a-b| / max|b|
KERNEL_TOL = 2e-2


class JaxEvents:
    """Counts of the jax.monitoring events the smoke reads: XLA backend
    compilations and persistent-cache hits and misses. One per process (a
    listener cannot be taken off again), created by whoever runs the legs."""
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"
    CACHE_MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.n = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda event, **kw: self.n.update([event]))
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, duration, **kw: self.n.update([event]))


class Leg:
    """One leg's verdict: named checks that all have to hold, plus the
    facts it prints."""

    def __init__(self):
        self.failed = []
        self.info = {}

    def check(self, name, ok, detail=""):
        if not ok:
            self.failed.append(f"{name}[{detail}]" if detail != "" else name)

    @property
    def ok(self):
        return not self.failed

    def line(self, name):
        facts = " ".join(f"{k}={_fmt(v)}" for k, v in self.info.items())
        if self.ok:
            return f"LEG {name} ok {facts}"
        return f"LEG {name} FAIL failed={','.join(self.failed)} {facts}"


def _fmt(v):
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v).replace(" ", "")


def _relerr(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def free_device_memory():
    """Delete every live device array (between legs, or bench configs, that
    each need most of the chip)."""
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()
    gc.collect()


def cache_entries():
    """(directory, number of entries) of the persistent compile cache."""
    from paddle_tpu.framework.compilation_cache import (
        cache_dir, ensure_persistent_cache)
    ensure_persistent_cache()
    d = cache_dir()
    return d, (len(os.listdir(d)) if d and os.path.isdir(d) else 0)


# ---------------------------------------------------------------------------
# device


def leg_device():
    from importlib import metadata
    import jax
    import jaxlib
    leg = Leg()
    dev = jax.devices()[0]
    leg.check("backend_is_tpu", jax.default_backend() == "tpu",
              jax.default_backend())
    d, n = cache_entries()
    leg.info.update(platform=dev.platform, kind=dev.device_kind,
                    count=len(jax.devices()), jax=jax.__version__,
                    jaxlib=jaxlib.__version__,
                    libtpu=metadata.version("libtpu"), cache_dir=d,
                    cache_entries=n)
    return leg


# ---------------------------------------------------------------------------
# train


def leg_train(cfg, events, *, batch, seq, param_dtype, moment_dtype,
              expect_kernels, mesh=None, timed_steps=4):
    """Returns (leg, step, losses). ``events`` is the process's JaxEvents.
    ``expect_kernels``: the flash kernel must be a Mosaic call in the
    compiled step (True on the chip; the CPU path routes to the XLA
    attention by shape and backend)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt_hybrid import HybridTrainStep

    leg = Leg()
    opt = paddle.optimizer.AdamW(
        2e-4, grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0),
        moment_dtype=moment_dtype)
    step = HybridTrainStep(cfg, opt, mesh=mesh, param_dtype=param_dtype)
    ids = jax.random.randint(jax.random.key(1), (batch, seq), 0,
                             cfg.vocab_size, jnp.int32)

    t0 = time.perf_counter()
    losses = [step(ids)]
    jax.block_until_ready(losses[0])
    leg.info["setup_s"] = time.perf_counter() - t0

    # the lowered text of THE executable that just ran (jit caches the
    # trace, so this neither retraces nor compiles)
    lr = jnp.asarray(opt.get_lr(), jnp.float32)
    text = step._jitted.lower(step._flat(step.params), step.opt_state, ids,
                              lr).as_text()
    n_mosaic = text.count(MOSAIC_CALL)
    leg.info["mosaic_calls"] = n_mosaic
    leg.check("flash_in_compiled_step", (n_mosaic > 0) == expect_kernels,
              n_mosaic)

    # the same steps timed twice: once ended by block_until_ready, once by
    # fetching the loss — they agree iff block_until_ready really blocks
    compiles0 = events.n[events.COMPILE]
    t0 = time.perf_counter()
    for _ in range(timed_steps):
        losses.append(step(ids))
    jax.block_until_ready(losses[-1])
    t_block = (time.perf_counter() - t0) / timed_steps
    t0 = time.perf_counter()
    for _ in range(timed_steps):
        losses.append(step(ids))
    jax.device_get(losses[-1])
    t_get = (time.perf_counter() - t0) / timed_steps
    leg.info.update(step_s_block=t_block, step_s_get=t_get)
    # one compilation of the step: the warm-up's. (Not jit's _cache_size():
    # on a mesh the step's outputs come back with normalised specs, a second
    # fast-path entry for the same executable.)
    recompiles = events.n[events.COMPILE] - compiles0
    leg.check("no_compilation_after_warm_up", recompiles == 0, recompiles)
    if expect_kernels and timed_steps >= 4:    # a wall-clock comparison only
        leg.check("block_until_ready_blocks",  # means something on the device
                  abs(t_block - t_get) <= 0.05 * max(t_block, t_get),
                  f"{t_block:.4f}vs{t_get:.4f}")

    losses = [float(x) for x in jax.device_get(losses)]
    leg.info["losses"] = ",".join(f"{x:.4f}" for x in losses)
    # seeded N(0, r) weights: the logits at init are ~N(0, H r^2), whose
    # expected cross-entropy is ln V + H r^2 / 2 (11.24 at 1.3B, not ln V)
    expected = math.log(cfg.vocab_size) + \
        0.5 * cfg.hidden_size * cfg.initializer_range ** 2
    leg.check("first_loss_is_the_init_entropy",
              abs(losses[0] - expected) < 0.1, f"{losses[0]:.4f}vs{expected:.4f}")
    leg.check("losses_finite", all(math.isfinite(x) for x in losses))
    # not "every step falls": AdamW at 2e-4 with no warm-up overshoots once
    # on this batch (1.3B on the chip: 10.15 -> 11.82 at step 3, then down)
    leg.check("loss_fell", losses[-1] < losses[0])
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        leg.info["peak_hbm_gb"] = stats["peak_bytes_in_use"] / 2 ** 30
    return leg, step, losses


# ---------------------------------------------------------------------------
# serve


class _LoweringRecorder:
    """Stands in for the engine's fused paged step: forwards every call and
    keeps the lowered text of each distinct dispatch shape — the executable
    ledger, read off the real dispatches."""

    def __init__(self, fn):
        self.fn = fn
        self.lowered = {}

    def __call__(self, *args, layout):
        shape = (layout.B, layout.T)        # ids [B, T], inside the buffer
        if shape not in self.lowered:
            self.lowered[shape] = self.fn.lower(*args,
                                                layout=layout).as_text()
        return self.fn(*args, layout=layout)


def _serve(cfg, params, prompts, max_new, **engine_kw):
    """One engine, the requests through submit()/run(). Returns the facts
    the serve checks read."""
    from paddle_tpu import profiler, serving
    traces0 = profiler.serving_counters()["paged_traces"]
    eng = serving.Engine(params=params, config=cfg, **engine_kw)
    rec = _LoweringRecorder(eng._paged_step)
    eng._paged_step = rec
    reqs = [serving.Request(p, max_new_tokens=max_new) for p in prompts]
    t0 = time.perf_counter()
    results = eng.run(reqs)
    return {
        "wall_s": time.perf_counter() - t0,
        "results": [results[r.request_id] for r in reqs],
        "lowered": rec.lowered,
        "step_fn": rec.fn,
        "traces": profiler.serving_counters()["paged_traces"] - traces0,
        "balance": eng.pool.balance(),
        "decode_shape": (eng.num_slots, 1),
        "chunk_shapes": {(1, c) for c in eng._chunk_ladder},
        "engine": eng,      # alive until the caller has read the devices
    }


def _check_served(leg, tag, run, max_new, fresh_executables=True):
    res = run["results"]
    leg.check(f"{tag}_all_finish_length",
              all(r.finish_reason == "length" and len(r.tokens) == max_new
                  for r in res),
              [(r.finish_reason, len(r.tokens)) for r in res])
    shapes = set(run["lowered"])
    leg.check(f"{tag}_two_executables",
              run["decode_shape"] in shapes and len(shapes) == 2
              and shapes - {run["decode_shape"]} <= run["chunk_shapes"],
              sorted(shapes))
    leg.check(f"{tag}_no_retrace",
              run["traces"] == (2 if fresh_executables else 0),
              run["traces"])
    bal = run["balance"]
    leg.check(f"{tag}_pool_balance",
              bal["conserved"] and bal["refcounts_accounted"], bal)


def reference_first_logits(cfg, params, prompts):
    """fp32 next-token logits [n, V] after each prompt from the TRAINING
    forward (gpt_hidden, whole prompt, right-padded: causal, so padding
    cannot reach the last real position) — the model's definition, against
    which the serving forward's first tokens are judged."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.gpt_hybrid import gpt_hidden
    lens = np.array([len(p) for p in prompts])
    ids = np.zeros((len(prompts), int(lens.max())), np.int32)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p

    @jax.jit
    def fwd(params, ids, last):
        hidden = gpt_hidden(params, ids, cfg)
        xlast = hidden[jnp.arange(ids.shape[0]), last].astype(jnp.float32)
        return xlast @ params["head_w"].astype(jnp.float32)

    return np.asarray(fwd(params, jnp.asarray(ids), jnp.asarray(lens - 1)))


def _check_first_tokens(leg, tag, ref_logits, results):
    """Each first token is the reference's arg-max up to numerical ties:
    its reference logit is within a tenth of the logits' spread of the
    maximum (random weights make near-ties; a wrong forward lands several
    spreads below)."""
    slack = [float(ref.max() - ref[r.tokens[0]]) / float(ref.std())
             for ref, r in zip(ref_logits, results)]
    leg.info[f"{tag}_first_tok_slack_max"] = max(slack)
    leg.check(f"{tag}_first_tokens_are_reference_argmax",
              all(s <= 0.1 for s in slack), [round(s, 3) for s in slack])


def leg_serve(cfg, params, *, prompt_lens, max_new, expect_kernels, seed=0):
    """Returns (leg, served): the prompts, the reference logits and the
    kernel-off engine's tokens, for a later leg to serve and judge alike."""
    import paddle_tpu as paddle
    from paddle_tpu.models.generation import generate_from_params

    leg = Leg()
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in prompt_lens]
    ref_logits = reference_first_logits(cfg, params, prompts)

    # default flags: paged layout, FLAGS_serving_paged_kernel True
    t0 = time.perf_counter()
    on = _serve(cfg, params, prompts, max_new)
    leg.info["setup_and_run_s"] = time.perf_counter() - t0
    del on["engine"]
    _check_served(leg, "kernel_on", on, max_new)
    dec = on["lowered"].get(on["decode_shape"], "")
    leg.info["decode_mosaic_calls"] = dec.count(MOSAIC_CALL)
    leg.check("decode_kernel_in_compiled_step",
              (MOSAIC_CALL in dec) == expect_kernels)
    _check_first_tokens(leg, "kernel_on", ref_logits, on["results"])

    saved = paddle.get_flags("FLAGS_serving_paged_kernel")
    paddle.set_flags({"FLAGS_serving_paged_kernel": False})
    try:
        off = _serve(cfg, params, prompts, max_new)
    finally:
        paddle.set_flags(saved)
    del off["engine"]
    # off the chip both engines resolve to the same (gather) executables
    _check_served(leg, "kernel_off", off, max_new,
                  fresh_executables=off["step_fn"] is not on["step_fn"])
    leg.check("kernel_off_has_no_kernel",
              all(MOSAIC_CALL not in t for t in off["lowered"].values()))
    _check_first_tokens(leg, "kernel_off", ref_logits, off["results"])

    tok_on = np.array([r.tokens for r in on["results"]])
    tok_off = np.array([r.tokens for r in off["results"]])
    # prefill never takes the kernel: the first tokens come from the same
    # math in both engines
    leg.check("first_token_on_eq_off", (tok_on[:, 0] == tok_off[:, 0]).all(),
              [tok_on[:, 0].tolist(), tok_off[:, 0].tolist()])
    agree = float((tok_on == tok_off).mean())
    leg.info["kernel_on_off_agreement"] = agree
    leg.check("kernel_on_off_agreement", agree >= MIN_KERNEL_AGREEMENT, agree)

    # the README's "bitwise identical to generate_from_params" contract, on
    # THIS backend, with the kernel off: reported, gated only on first tokens
    # through the reference above (bf16 GEMMs at [1,chunk] and whole-prompt
    # shapes need not reduce alike)
    tok_gen = np.array([
        np.asarray(generate_from_params(params, p[None], cfg,
                                        max_new_tokens=max_new)._data
                   )[0, len(p):] for p in prompts])
    leg.info["gen_first_token_eq"] = int((tok_gen[:, 0] == tok_off[:, 0]).sum())
    leg.info["gen_identical_requests"] = int(
        (tok_gen == tok_off).all(axis=1).sum())
    leg.info["gen_token_agreement"] = float((tok_gen == tok_off).mean())
    leg.info["serve_wall_s_on"] = on["wall_s"]
    leg.info["serve_wall_s_off"] = off["wall_s"]
    return leg, {"prompts": prompts, "ref_logits": ref_logits,
                 "tok_off": tok_off}


# ---------------------------------------------------------------------------
# kernels


def _kernel_case(leg, name, fn, args, want, expect_mosaic, tol=KERNEL_TOL):
    """Lower + run one kernel entry point, compare with its reference. A
    compiler refusal is this case's failure, reported with the others."""
    import jax
    try:
        jitted = jax.jit(fn)
        n_mosaic = jitted.lower(*args).as_text().count(MOSAIC_CALL)
        got = jitted(*args)
        errs = [_relerr(g, w) for g, w in
                zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want))]
    except Exception as e:  # noqa: BLE001 — boundary: report every kernel
        traceback.print_exc()
        leg.check(name, False, f"{type(e).__name__}: {str(e)[:300]}")
        return
    leg.info[name] = f"{max(errs):.2e}"
    leg.check(f"{name}_through_mosaic", (n_mosaic > 0) == expect_mosaic,
              n_mosaic)
    leg.check(f"{name}_matches_reference", max(errs) <= tol, errs)


def leg_kernels(cfg, *, batch, seq, num_slots, page_size, max_seq_len,
                interpret):
    """Each single-chip Pallas kernel at this config's shapes against its
    own reference. ``interpret`` is what the paged and quant entry points
    take (False on the chip); flash picks it from the backend itself."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.blockwise_attention import blockwise_attention
    from paddle_tpu.ops.pallas_kernels.flash_attention import (
        flash_attention_bshd)
    from paddle_tpu.ops.pallas_kernels.quant_gemm import quant_gemm_kernel
    from paddle_tpu.serving.paged_attention import (
        pad_lanes, paged_attention_read, paged_decode_attention,
        paged_decode_attention_q, pool_head_dim)

    leg = Leg()
    mosaic = not interpret
    nh = cfg.num_heads
    H = cfg.hidden_size
    d = H // nh
    rng = np.random.default_rng(0)

    # flash fwd + bwd; d/2 takes the pad-to-128-lanes path
    def attn_grads(attn):
        def f(q, k, v, w):
            def loss(q, k, v):
                o = attn(q, k, v)
                return jnp.sum(o.astype(jnp.float32)
                               * w.astype(jnp.float32)), o
            (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                           has_aux=True)(q, k, v)
            return (o,) + g
        return f

    for D in (d, d // 2):
        args = tuple(jnp.asarray(rng.standard_normal((batch, seq, nh, D)),
                                 jnp.bfloat16) for _ in range(4))
        want = jax.jit(attn_grads(
            lambda q, k, v: blockwise_attention(q, k, v, causal=True)))(*args)
        _kernel_case(leg, f"flash_fwd_bwd_d{D}",
                     attn_grads(lambda q, k, v: flash_attention_bshd(
                         q, k, v, True)), args, want, mosaic)

    # paged decode, full-precision and int8 pool, every slot at a different
    # depth of its page list (first page, page boundary, last position);
    # the engine's call: the last layer of a stacked pool, addressed in
    # place by the kernel's index_map and by the gather's start indices.
    # Then twice the heads of 5/8 the head_dim in the same hidden size
    # (GPT-3 2.7B's 32 heads of 80 beside 1.3B's 16 of 128): the pool holds
    # it in 128 lanes, the pad zeros, and the kernel widens the query
    MP = max_seq_len // page_size
    P = num_slots * MP + 1
    table = jnp.asarray(rng.permutation(np.arange(1, P)).reshape(
        num_slots, MP), jnp.int32)
    pos = jnp.asarray(np.linspace(0, max_seq_len - 1, num_slots).round(),
                      jnp.int32).at[1].set(page_size - 1).at[2].set(page_size)
    layer = jnp.asarray(1, jnp.int32)
    ksc, vsc = (jnp.asarray(rng.uniform(0.005, 0.02, (P,)), jnp.float32)
                for _ in range(2))

    def gather_read(q, kc, vc, table, pos, *scales):
        return paged_attention_read(q[:, None], kc, vc, layer, table,
                                    pos[:, None], page_size, False,
                                    jnp.float32, *scales)[:, 0]

    for tag, heads, dim in (("", nh, d), (f"_d{d * 5 // 8}", 2 * nh,
                                          d * 5 // 8)):
        q = jnp.asarray(rng.standard_normal((num_slots, heads, dim)),
                        jnp.float32)
        shape = (2, P, page_size, heads, dim)
        like = jnp.zeros((1, pool_head_dim(dim)))
        kc, vc = (pad_lanes(jnp.asarray(rng.standard_normal(shape),
                                        jnp.bfloat16), like)
                  for _ in range(2))
        kq, vq = (pad_lanes(jnp.asarray(rng.integers(-127, 128, shape),
                                        jnp.int8), like) for _ in range(2))
        args = (q, kc, vc, table, pos)
        _kernel_case(leg, "paged_decode" + tag,
                     lambda *a: paged_decode_attention(
                         *a, page_size=page_size, layer=layer,
                         interpret=interpret),
                     args, jax.jit(gather_read)(*args), mosaic)
        args = (q, kq, vq, table, pos, ksc, vsc)
        _kernel_case(leg, "paged_decode_q" + tag,
                     lambda *a: paged_decode_attention_q(
                         *a, page_size=page_size, layer=layer,
                         interpret=interpret),
                     args, jax.jit(gather_read)(*args), mosaic)

    # weight-only int8 GEMM at the qkv and ffn-up widths, one decode batch
    for F in (3 * H, cfg.ffn_mult * H):
        x = jnp.asarray(rng.standard_normal((num_slots, H)), jnp.bfloat16)
        wq = jnp.asarray(rng.integers(-127, 128, (H, F)), jnp.int8)
        s = jnp.asarray(rng.uniform(0.001, 0.01, (F,)), jnp.float32)
        want = (x @ wq.astype(x.dtype)) * s.astype(x.dtype)
        _kernel_case(leg, f"quant_gemm_F{F}",
                     lambda x, wq, s: quant_gemm_kernel(
                         x, wq, s, interpret=interpret),
                     (x, wq, s), want, mosaic)
    return leg


# ---------------------------------------------------------------------------
# four chips


def _device_bytes():
    import jax
    return [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in jax.devices()]


def _check_balance(leg, tag, n=4):
    """No device holds more than half of the bytes, none holds nothing:
    work that silently lands on the first chip shows here."""
    used = _device_bytes()[:n]
    leg.info[f"{tag}_device_gb"] = ",".join(f"{b / 2 ** 30:.2f}" for b in used)
    leg.check(f"{tag}_balanced",
              min(used) > 0 and max(used) <= 0.5 * sum(used), used)


def leg_four_chip(cfg, events, *, batch, seq, one_chip_first_loss, params,
                  served, max_new, param_dtype, moment_dtype, expect_kernels):
    """``params`` are host arrays (the one-chip legs' trained tree),
    ``served`` what leg_serve returned for them."""
    import __graft_entry__
    from paddle_tpu.distributed import env as dist_env

    leg = Leg()
    mesh = dist_env.create_hybrid_mesh(dp=2, mp=2)
    sub, step, losses = leg_train(
        cfg, events, batch=batch, seq=seq, param_dtype=param_dtype,
        moment_dtype=moment_dtype, expect_kernels=expect_kernels, mesh=mesh,
        timed_steps=1)
    leg.failed += [f"train_{f}" for f in sub.failed]
    leg.info["train_losses"] = sub.info["losses"]
    leg.info["train_setup_s"] = sub.info["setup_s"]
    leg.check("train_first_loss_matches_one_chip",
              abs(losses[0] - one_chip_first_loss) < 2e-2,
              f"{losses[0]:.4f}vs{one_chip_first_loss:.4f}")
    _check_balance(leg, "train")
    del step
    dist_env.set_mesh(None)
    free_device_memory()

    run = _serve(cfg, params, served["prompts"], max_new, mp=4)
    _check_served(leg, "mp4", run, max_new)
    _check_balance(leg, "mp4")
    _check_first_tokens(leg, "mp4", served["ref_logits"], run["results"])
    tok = np.array([r.tokens for r in run["results"]])
    leg.info["mp4_vs_one_chip_agreement"] = float(
        (tok == served["tok_off"]).mean())
    del run
    free_device_memory()

    __graft_entry__.dryrun_multichip(4)
    leg.info["dryrun_device_gb"] = ",".join(
        f"{b / 2 ** 30:.2f}" for b in _device_bytes())
    dist_env.set_mesh(None)
    return leg


# ---------------------------------------------------------------------------


def main():
    t_start = time.perf_counter()
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke.py: no TPU — jax.default_backend() is "
                 f"{backend!r}; nothing was run")
    import jax.numpy as jnp
    import paddle_tpu  # noqa: F401
    from paddle_tpu.models.gpt import GPT_CONFIGS

    events = JaxEvents()
    cfg = dataclasses.replace(GPT_CONFIGS[MODEL], max_seq_len=SEQ,
                              use_flash=True, compute_dtype="bfloat16",
                              remat=True)
    bf16 = dict(param_dtype=jnp.bfloat16, moment_dtype="bfloat16")
    legs = {}

    def run(name, fn):
        """Run one leg, print its line. Returns what the leg hands on to
        later legs; None when it raised — a failed leg, and the legs after
        it still run."""
        t0 = time.perf_counter()
        try:
            out = fn()
            leg, *handed_on = out if isinstance(out, tuple) else (out,)
        except Exception as e:  # noqa: BLE001 — boundary: report, go on
            traceback.print_exc()
            leg, handed_on = Leg(), None
            leg.check("raised", False,
                      f"{type(e).__name__}: {' '.join(str(e).split())[:400]}")
        leg.info["leg_s"] = time.perf_counter() - t0
        legs[name] = leg
        print(leg.line(name), flush=True)
        return handed_on

    run("device", leg_device)

    trained = run("train", lambda: leg_train(
        cfg, events, batch=BATCH, seq=SEQ, expect_kernels=True, **bf16))
    if trained:
        step, losses = trained
        params, first_loss = step.params, losses[0]
        step.opt_state = None       # the hand-off keeps the params only
        del step, trained
    else:                           # train failed: serve seeded weights
        from paddle_tpu.models.gpt_hybrid import init_gpt_params
        params = init_gpt_params(cfg, jax.random.key(0), jnp.bfloat16)
        first_loss = float("nan")
    gc.collect()

    served = run("serve", lambda: leg_serve(
        cfg, params, prompt_lens=PROMPT_LENS, max_new=MAX_NEW,
        expect_kernels=True))

    from paddle_tpu import flags as _flags
    serve_flags = _flags.get_flags(["FLAGS_serving_slots",
                                    "FLAGS_serving_page_size"])
    run("kernels", lambda: leg_kernels(
        cfg, batch=BATCH, seq=SEQ,
        num_slots=serve_flags["FLAGS_serving_slots"],
        page_size=serve_flags["FLAGS_serving_page_size"],
        max_seq_len=SEQ, interpret=False))

    if jax.device_count() >= 4 and served:
        host_params = jax.device_get(params)
        del params
        free_device_memory()
        run("four_chip", lambda: leg_four_chip(
            cfg, events, batch=BATCH, seq=SEQ, one_chip_first_loss=first_loss,
            params=host_params, served=served[0], max_new=MAX_NEW,
            expect_kernels=True, **bf16))
    else:
        print(f"LEG four_chip not_run devices={jax.device_count()}",
              flush=True)

    d, n = cache_entries()
    print(f"CACHE dir={d} entries={n} "
          f"hits={events.n[events.CACHE_HIT]} "
          f"misses={events.n[events.CACHE_MISS]} "
          f"total_s={time.perf_counter() - t_start:.1f}", flush=True)
    ok = all(leg.ok for leg in legs.values())
    dev = jax.devices()[0]
    print(json.dumps({"ok": ok, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
