"""Pallas TPU flash-attention forward kernel.

Replaces the reference's fused CUDA flash_attention (ref: paddle/phi/kernels/
gpu/flash_attn_kernel.cu capability) with a TPU-native kernel: the grid walks
(batch·head, q-block, k-block); per q-block online-softmax state (m, l, acc)
lives in VMEM scratch across the k-block sweep, scores are computed on the MXU
in fp32, and causal q<k blocks are skipped entirely (predicated grid steps).

Supported in-kernel (ref: python/paddle/nn/functional/flash_attention.py:125
`flash_attention`, :269 `flash_attn_unpadded`):
  - causal masking (block-skipped, not just masked)
  - segment ids (packed varlen batches / padding masks): per-token int ids for
    q and kv; tokens attend only within their segment
  - additive bias / mask `ab` broadcastable as (B|1, H|1, Sq, Sk), added after
    the softmax scale (matches the composed XLA path's `logits*scale + mask`)
  - dropout on the normalized probabilities via the TPU PRNG, seeded per
    (batch·head, q-block, k-block) so the backward regenerates identical bits

TPU layout notes (Mosaic (8,128) tiling rule): every pallas block must have
its last two dims divisible by (8, 128) or equal to the full array dims.
Per-row statistics (LSE) travel lane-broadcast as [bq, 128] tiles — shaped
(BH, Sq, 128) with all lanes equal; the wrapper slices lane 0 off for the
compact (BH, Sq) residual. Segment ids use the standard TPU layout: q ids
lane-broadcast (B, Sq, 128), kv ids sublane-broadcast (B, 8, Sk).

Backward: pallas kernels in flash_attention_bwd.py (LSE saved by this
forward, scores recomputed blockwise on the MXU). The differentiable blockwise
XLA path (ops/blockwise_attention.py) remains as the interpret/fallback
reference.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..blockwise_attention import blockwise_attention
from .flash_attention_bwd import (LANES, SUBLANES, _NEG_INF, dropout_keep,
                                  flash_attention_backward, segment_mask)

logger = logging.getLogger("paddle_tpu.flash_attention")


def pick_block(seq_len, preferred=256):
    """Largest power-of-two block <= preferred that divides seq_len (Mosaic
    wants >=128 lanes; smaller seqs fall back to the XLA path via
    flash_supported)."""
    for b in (preferred, 256, 128):
        if b <= preferred and seq_len % b == 0:
            return b
    return None


def flash_supported(q_shape, kv_seq=None, why="", varlen=False):
    """THE routing predicate for the pallas flash path — used by every
    caller (nn.functional SDPA, models/gpt, bench) so gating can't drift.
    Logs the reason when the kernel is skipped (a silent fallback cost
    round 2 its perf evidence). varlen packs + pads internally, so only the
    backend and head_dim gates apply to it."""
    reasons = []
    if jax.default_backend() != "tpu":
        reasons.append("backend is not TPU")
    else:
        seq, d = q_shape[1], q_shape[-1]
        if d > 256:
            reasons.append(f"head_dim {d} > 256")
        if not varlen:
            if pick_block(seq) is None:
                reasons.append(f"q seq_len {seq} not a multiple of 128")
            if kv_seq is not None and pick_block(kv_seq) is None:
                reasons.append(f"kv seq_len {kv_seq} not a multiple of 128")
    if reasons:
        logger.info("flash attention fallback to XLA path%s: %s",
                    f" ({why})" if why else "", "; ".join(reasons))
        return False
    return True


def _fwd_kernel(*refs, causal, nq, nk, bq, bk, scale, dropout_p, has_bias,
                has_seg, with_lse):
    refs = list(refs)
    seed_ref = refs.pop(0) if dropout_p > 0.0 else None
    q_ref, k_ref, v_ref = refs[:3]
    refs = refs[3:]
    ab_ref = refs.pop(0) if has_bias else None
    qseg_ref = refs.pop(0) if has_seg else None
    kseg_ref = refs.pop(0) if has_seg else None
    o_ref = refs.pop(0)
    lse_ref = refs.pop(0) if with_lse else None
    m_scr, l_scr, acc_scr = refs

    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    neg_inf = jnp.float32(_NEG_INF)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, neg_inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal block skip: run unless the whole block is above the diagonal
    run = (ki * bk < (qi + 1) * bq) if causal else (ki >= 0)

    @pl.when(run)
    def _block():
        q = q_ref[0, :, :].astype(jnp.float32)      # [bq, D]
        k = k_ref[0, :, :].astype(jnp.float32)      # [bk, D]
        v = v_ref[0, :, :].astype(jnp.float32)      # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.float32(scale)
        if has_bias:
            s = s + ab_ref[0, 0, :, :].astype(jnp.float32)
        if has_seg:
            s = jnp.where(segment_mask(qseg_ref, kseg_ref, bq, bk), s, neg_inf)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, neg_inf)
        # m/l live lane-broadcast in (bq, 128) scratch (TPU tiling needs
        # lane dim 128); all 128 lanes hold the same value.
        m_prev = jnp.max(m_scr[:, :], axis=1, keepdims=True)     # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                       # [bq, bk]
        corr = jnp.exp(m_prev - m_new)               # [bq, 1]
        l_prev = jnp.max(l_scr[:, :], axis=1, keepdims=True)
        # normalizer uses the PRE-dropout sum: out = sum(drop(P) @ V) with
        # P = softmax (dropout after normalization, like the reference)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        if dropout_p > 0.0:
            keep = dropout_keep(seed_ref[0], b, qi, ki, (bq, bk), dropout_p)
            p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        acc_scr[:, :] = acc_scr[:, :] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[:, :] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:, :] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        m = jnp.max(m_scr[:, :], axis=1, keepdims=True)       # [bq, 1]
        # fully-masked rows (padding segments): every s was _NEG_INF, so
        # p=exp(0)=1 polluted acc/l — zero the output and push LSE to +big
        # so the backward's exp(s - lse) underflows to exactly 0
        masked = m <= jnp.float32(0.5 * _NEG_INF)
        l = jnp.maximum(jnp.max(l_scr[:, :], axis=1, keepdims=True),
                        jnp.float32(1e-30))
        o_ref[0, :, :] = jnp.where(
            masked, 0.0, acc_scr[:, :] / l).astype(o_ref.dtype)
        if with_lse:
            lse = jnp.where(masked, -jnp.float32(_NEG_INF),
                            m + jnp.log(jnp.maximum(
                                jnp.max(l_scr[:, :], axis=1, keepdims=True),
                                1e-30)))
            # lane-broadcast write: (bq, 128) tile, every lane equal
            lse_ref[0, :, :] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _broadcast_index(dim, idx):
    """Index-map helper for bias dims that may be 1 (broadcast)."""
    return 0 if dim == 1 else idx


def _pallas_forward(q, k, v, causal, block_q=256, block_k=256,
                    with_residuals=False, interpret=False, bias=None,
                    segment_ids=None, dropout_p=0.0, dropout_seed=None,
                    scale=None):
    """q,k,v: [B, S, H, D] -> [B, S, H, D]. Head dim padded to a lane (128)
    multiple — zero columns don't change scores or outputs.

    bias: optional additive (B|1, H|1, Sq, Sk) term (mask as -inf entries).
    segment_ids: optional (q_ids, kv_ids) int32 [B, Sq] / [B, Sk]; attention
      only within equal ids (packed varlen / padding).
    dropout_p/dropout_seed: in-kernel dropout on normalized probabilities.
    With with_residuals, also returns the bh-layout tensors + LSE the pallas
    backward consumes.
    """
    if q.dtype == jnp.float64:
        # kernel accumulates in fp32 regardless; f64 only appears via the
        # framework's global x64 flag, never as a deliberate attention dtype
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    D0 = q.shape[-1]
    if D0 % 128 != 0:
        pad = 128 - D0 % 128
        q, k, v = (jnp.pad(t, ((0, 0), (0, 0), (0, 0), (0, pad)))
                   for t in (q, k, v))
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    block_q = pick_block(Sq, block_q) or min(block_q, Sq)
    block_k = pick_block(Sk, block_k) or min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (
        f"seq lens ({Sq}, {Sk}) not divisible by blocks "
        f"({block_q}, {block_k}); gate callers with flash_supported()")
    nq, nk = Sq // block_q, Sk // block_k
    if dropout_p:
        # same packed-seed envelope as the backward: dropout_keep packs the
        # q/k block indices into 10 bits each of one prng_seed word
        assert nq < 1024 and nk < 1024, (
            f"flash-attention dropout PRNG seed packs q/k block indices into "
            f"10 bits each; got num_q_blocks={nq}, num_k_blocks={nk} — raise "
            f"block_q/block_k so both stay below 1024")
    scale = D0 ** -0.5 if scale is None else scale

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)

    qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)
    grid = (B * H, nq, nk)
    interpret = interpret or jax.default_backend() != "tpu"
    if float(dropout_p) > 0.0 and interpret:
        raise NotImplementedError(
            "in-kernel dropout uses the TPU PRNG, which interpret mode does "
            "not emulate; off-TPU dropout routes through the composed XLA "
            "path (nn.functional.scaled_dot_product_attention)")
    has_bias = bias is not None
    has_seg = segment_ids is not None
    dropout_p = float(dropout_p)
    kw = dict(causal=causal, nq=nq, nk=nk, bq=block_q, bk=block_k, scale=scale,
              dropout_p=dropout_p, has_bias=has_bias, has_seg=has_seg,
              with_lse=with_residuals)

    operands = []
    in_specs = []
    if dropout_p > 0.0:
        assert dropout_seed is not None
        operands.append(jnp.asarray(dropout_seed, jnp.int32).reshape(1))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands += [qb, kb, vb]
    in_specs += [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
    ]
    if has_bias:
        assert bias.ndim == 4 and bias.shape[-2:] == (Sq, Sk), bias.shape
        Bb, Hb = bias.shape[:2]
        operands.append(bias)
        in_specs.append(pl.BlockSpec(
            (1, 1, block_q, block_k),
            lambda b, i, j: (_broadcast_index(Bb, b // H),
                             _broadcast_index(Hb, b % H), i, j)))
    if has_seg:
        qs, ks = segment_ids
        assert qs.shape == (B, Sq) and ks.shape == (B, Sk)
        operands.append(jax.lax.broadcast_in_dim(
            qs.astype(jnp.int32), (B, Sq, LANES), (0, 1)))
        in_specs.append(pl.BlockSpec((1, block_q, LANES),
                                     lambda b, i, j: (b // H, i, 0)))
        operands.append(jax.lax.broadcast_in_dim(
            ks.astype(jnp.int32), (B, SUBLANES, Sk), (0, 2)))
        in_specs.append(pl.BlockSpec((1, SUBLANES, block_k),
                                     lambda b, i, j: (b // H, 0, j)))

    o_spec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    if with_residuals:
        # lane-broadcast LSE: (8,128)-tileable; lane 0 sliced off below so
        # the saved residual is the compact (BH, Sq)
        out_shape = (jax.ShapeDtypeStruct(qb.shape, q.dtype),
                     jax.ShapeDtypeStruct((B * H, Sq, LANES), jnp.float32))
        out_specs = (o_spec, pl.BlockSpec((1, block_q, LANES),
                                          lambda b, i, j: (b, i, 0)))
    else:
        out_shape = jax.ShapeDtypeStruct(qb.shape, q.dtype)
        out_specs = o_spec
    scratch = [
        pltpu.VMEM((block_q, LANES), jnp.float32),
        pltpu.VMEM((block_q, LANES), jnp.float32),
        pltpu.VMEM((block_q, D), jnp.float32),
    ]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    # Mosaic rejects x64-typed index math; the framework enables x64 globally
    # for dtype parity, so pin 32-bit types inside the kernel trace. The
    # scope names the device op (%flash_fwd.N in a chip trace) whatever
    # wraps the call: custom_vjp, remat, a scan.
    with jax.enable_x64(False), jax.named_scope("flash_fwd"):
        result = pl.pallas_call(
            functools.partial(_fwd_kernel, **kw),
            out_shape=out_shape,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
            compiler_params=params,
            interpret=interpret,
        )(*operands)
    if with_residuals:
        out, lse = result
        lse = lse[:, :, 0]
    else:
        out, lse = result, None
    res = (qb, kb, vb, out, lse, scale) if with_residuals else None
    out = out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    out = out[..., :D0] if D0 != D else out
    return (out, res) if with_residuals else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 6, 8, 9, 10))
def flash_attention_bshd(q, k, v, causal=True, bias=None, segment_ids=None,
                         dropout_p=0.0, dropout_seed=None, scale=None,
                         block_q=256, block_k=256):
    """Differentiable flash attention, [B, S, H, D] layout.

    bias and segment_ids participate in the forward and in the recomputed
    backward scores but receive no gradients (masks are constants; the
    reference's flash_attn likewise returns no mask/bias grad).
    block_q/block_k tile the pallas grid (both clamped to S; must divide
    it) — the autotuning surface for MFU sweeps.
    """
    return _pallas_forward(q, k, v, causal, block_q=block_q, block_k=block_k,
                           bias=bias, segment_ids=segment_ids,
                           dropout_p=dropout_p, dropout_seed=dropout_seed,
                           scale=scale)


def _vjp_fwd(q, k, v, causal, bias, segment_ids, dropout_p, dropout_seed,
             scale, block_q, block_k):
    out, res = _pallas_forward(q, k, v, causal, block_q=block_q,
                               block_k=block_k, with_residuals=True,
                               bias=bias, segment_ids=segment_ids,
                               dropout_p=dropout_p, dropout_seed=dropout_seed,
                               scale=scale)
    # dtype carried as a zero-length proto array (residuals must be jax types)
    return out, (res, bias, segment_ids, dropout_seed, q.shape,
                 jnp.zeros((0,), q.dtype))


def _vjp_bwd(causal, dropout_p, _scale_arg, block_q, block_k, residuals, g):
    ((qb, kb, vb, ob, lse, scale), bias, segment_ids, dropout_seed,
     (B, Sq, H, D0), dt_proto) = residuals
    in_dtype = dt_proto.dtype
    Sk = kb.shape[1]
    D = qb.shape[-1]
    gb = g
    if D != D0:
        gb = jnp.pad(g, ((0, 0), (0, 0), (0, 0), (0, D - D0)))
    gb = gb.transpose(0, 2, 1, 3).reshape(B * H, Sq, D).astype(qb.dtype)
    interpret = jax.default_backend() != "tpu"
    dqb, dkb, dvb = flash_attention_backward(
        qb, kb, vb, ob, lse, gb, scale, causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
        bias=bias, segment_ids=segment_ids, num_heads=H,
        dropout_p=dropout_p, dropout_seed=dropout_seed)

    def from_bh(x, S):
        x = x.reshape(B, H, S, D).transpose(0, 2, 1, 3).astype(in_dtype)
        return x[..., :D0] if D != D0 else x

    # bias/segment_ids/dropout_seed are constants: None = zero cotangent
    return (from_bh(dqb, Sq), from_bh(dkb, Sk), from_bh(dvb, Sk),
            None, None, None)


flash_attention_bshd.defvjp(_vjp_fwd, _vjp_bwd)


def flash_attention_varlen(q, k, v, cu_seqlens_q, cu_seqlens_k, causal=True,
                           scale=None, dropout_p=0.0, dropout_seed=None,
                           block=256):
    """Packed varlen flash attention (ref: flash_attn_unpadded,
    python/paddle/nn/functional/flash_attention.py:269).

    q, k, v: [total_tokens, H, D] packed sequences; cu_seqlens_*: [n_seqs+1]
    cumulative token offsets. Returns [total_q_tokens, H, D]. Tokens are
    padded to a block multiple internally; padding lives in its own segment
    id so it never attends anywhere.
    """
    Tq, H, D = q.shape
    Tk = k.shape[0]

    def pad_to_block(x, T):
        rem = (-T) % block
        return (jnp.pad(x, ((0, rem),) + ((0, 0),) * (x.ndim - 1)), T + rem)

    qp, Tq_p = pad_to_block(q, Tq)
    kp, Tk_p = pad_to_block(k, Tk)
    vp, _ = pad_to_block(v, Tk)
    # token t belongs to segment searchsorted(cu, t, 'right'); padding gets
    # distinct ids on q (-1) vs kv (-2) so padded rows match nothing
    tq = jnp.arange(Tq_p, dtype=jnp.int32)
    tk = jnp.arange(Tk_p, dtype=jnp.int32)
    qseg = jnp.where(tq < Tq,
                     jnp.searchsorted(cu_seqlens_q, tq, side="right")
                     .astype(jnp.int32), -1)
    kseg = jnp.where(tk < Tk,
                     jnp.searchsorted(cu_seqlens_k, tk, side="right")
                     .astype(jnp.int32), -2)
    # packed layout: causality is per-segment; token offsets within a batch
    # row are monotone inside each segment, so global positional causality
    # composes correctly with the segment mask as long as paired q/k segments
    # start at the same offset (cu_seqlens_q == cu_seqlens_k), the
    # flash_attn_unpadded contract for causal=True.
    out = flash_attention_bshd(qp[None], kp[None], vp[None], causal,
                               None, (qseg[None], kseg[None]),
                               dropout_p, dropout_seed, scale)
    return out[0, :Tq]


def flash_attention_interpret(q, k, v, causal=True, block_q=256, block_k=256,
                              **kw):
    """Interpret-mode forward (+ residuals) so kernel numerics are testable
    on CPU without a TPU."""
    return _pallas_forward(q, k, v, causal, block_q=block_q, block_k=block_k,
                           with_residuals=True, interpret=True, **kw)
