"""Pallas TPU flash-attention backward kernels.

Standard flash-attention backward (Dao et al.) mapped to the TPU memory
hierarchy: the forward saves only O and the per-row logsumexp (LSE); the
backward recomputes score blocks on the MXU in fp32 and accumulates dQ (one
kernel, k-sweep in VMEM scratch) and dK/dV (one kernel, q-sweep in VMEM
scratch). Nothing S×S ever touches HBM, and causal off-diagonal blocks are
skipped via predicated grid steps — same blocking discipline as the forward
kernel in flash_attention.py.

Score recomputation applies the same bias / segment-id masking as the
forward, and dropout regenerates bit-identical keep masks by seeding the TPU
PRNG with the same (batch·head, q-block, k-block) triple the forward used.
With dropout, ``dP = keep/(1-p) * (dO·Vᵀ)`` and ``dV += (keep/(1-p)*P)ᵀ·dO``
(the softmax-backward identity ``Σ_k P dP = rowsum(dO∘O)`` still holds since
O was produced by the dropped probabilities).

Per-row vectors (LSE, delta) are fed lane-broadcast as (BH, Sq, 128) tiles —
Mosaic's (8,128) tiling rule forbids a (1, block_q) block over a (BH, Sq)
array — and reduced back to [bq, 1] inside the kernel with a lane-max (all
lanes equal).

Replaces the reference's fused CUDA flash_attn_grad kernel (ref: paddle/phi/
kernels/gpu/flash_attn_grad_kernel.cu capability).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
LANES = 128
SUBLANES = 8


def dropout_keep(seed, b, qi, ki, shape, dropout_p):
    """Regenerable per-block keep mask: seed the TPU PRNG with the grid
    coordinates so forward and both backward kernels draw identical bits.

    Mosaic accepts at most TWO prng_seed operands on current runtimes
    ("Setting seed with more than 2 values is not supported"), so the
    three grid coordinates are packed into one word: q/k block indices
    stay < 2^10 for every supported seq/block combination, and the
    batch*heads index wrapping at 2^11 only makes distant blocks reuse a
    mask stream — deterministic, and identical in fwd and bwd."""
    pltpu.prng_seed(seed, (b << 20) + (qi << 10) + ki)
    bits = pltpu.prng_random_bits(shape)  # int32
    threshold = jnp.int32(
        jnp.iinfo(jnp.int32).min + dropout_p * 2.0 ** 32)
    return bits >= threshold


def segment_mask(qseg_ref, kseg_ref, bq, bk):
    """[bq, bk] bool mask from lane-broadcast q ids (block [1, bq, LANES])
    and sublane-broadcast kv ids (block [1, SUBLANES, bk])."""
    assert bk % LANES == 0, f"block_k={bk} must be a multiple of {LANES}"
    qs = jnp.tile(qseg_ref[0, :, :], (1, bk // LANES))   # [bq, bk]
    ks = kseg_ref[0, :1, :]                              # [1, bk]
    return qs == ks


def _row_stat(ref):
    """Collapse a lane-broadcast [bq, LANES] block to [bq, 1] (lanes equal)."""
    return jnp.max(ref[0, :, :].astype(jnp.float32), axis=1, keepdims=True)


def _parse_refs(refs, has_bias, has_seg, dropout_p, n_out):
    refs = list(refs)
    seed_ref = refs.pop(0) if dropout_p > 0.0 else None
    q_ref, k_ref, v_ref, do_ref = refs[:4]
    refs = refs[4:]
    ab_ref = refs.pop(0) if has_bias else None
    qseg_ref = refs.pop(0) if has_seg else None
    kseg_ref = refs.pop(0) if has_seg else None
    lse_ref, delta_ref = refs[:2]
    outs = refs[2:2 + n_out]
    scratch = refs[2 + n_out:]
    return (seed_ref, q_ref, k_ref, v_ref, do_ref, ab_ref, qseg_ref, kseg_ref,
            lse_ref, delta_ref, outs, scratch)


def _recompute_p(q_ref, k_ref, lse_ref, ab_ref, qseg_ref, kseg_ref,
                 qi, ki, bq, bk, scale, causal):
    q = q_ref[0, :, :].astype(jnp.float32)              # [bq, D]
    k = k_ref[0, :, :].astype(jnp.float32)              # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * jnp.float32(scale)
    if ab_ref is not None:
        s = s + ab_ref[0, 0, :, :].astype(jnp.float32)
    if qseg_ref is not None:
        s = jnp.where(segment_mask(qseg_ref, kseg_ref, bq, bk), s,
                      jnp.float32(_NEG_INF))
    if causal:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(k_pos <= q_pos, s, jnp.float32(_NEG_INF))
    lse = _row_stat(lse_ref)                            # [bq, 1]
    return q, k, jnp.exp(s - lse)                       # p: [bq, bk]


def _dq_kernel(*refs, causal, nk, bq, bk, scale, dropout_p, has_bias,
               has_seg):
    (seed_ref, q_ref, k_ref, v_ref, do_ref, ab_ref, qseg_ref, kseg_ref,
     lse_ref, delta_ref, (dq_ref,), (dq_scr,)) = _parse_refs(
        refs, has_bias, has_seg, dropout_p, 1)
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = (ki * bk < (qi + 1) * bq) if causal else (ki >= 0)

    @pl.when(run)
    def _block():
        _, k, p = _recompute_p(q_ref, k_ref, lse_ref, ab_ref, qseg_ref,
                               kseg_ref, qi, ki, bq, bk, scale, causal)
        do = do_ref[0, :, :].astype(jnp.float32)        # [bq, D]
        v = v_ref[0, :, :].astype(jnp.float32)          # [bk, D]
        delta = _row_stat(delta_ref)                    # [bq, 1]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = dropout_keep(seed_ref[0], b, qi, ki, (bq, bk), dropout_p)
            dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
        ds = p * (dp - delta) * jnp.float32(scale)
        dq_scr[:, :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, :, :] = dq_scr[:, :].astype(dq_ref.dtype)


def _dkv_kernel(*refs, causal, nq, bq, bk, scale, dropout_p, has_bias,
                has_seg):
    (seed_ref, q_ref, k_ref, v_ref, do_ref, ab_ref, qseg_ref, kseg_ref,
     lse_ref, delta_ref, (dk_ref, dv_ref), (dk_scr, dv_scr)) = _parse_refs(
        refs, has_bias, has_seg, dropout_p, 2)
    b = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # causal: q-block contributes to this k-block only when it reaches the
    # diagonal ((qi+1)*bq > ki*bk)
    run = ((qi + 1) * bq > ki * bk) if causal else (qi >= 0)

    @pl.when(run)
    def _block():
        q, _, p = _recompute_p(q_ref, k_ref, lse_ref, ab_ref, qseg_ref,
                               kseg_ref, qi, ki, bq, bk, scale, causal)
        do = do_ref[0, :, :].astype(jnp.float32)        # [bq, D]
        v = v_ref[0, :, :].astype(jnp.float32)          # [bk, D]
        delta = _row_stat(delta_ref)                    # [bq, 1]
        if dropout_p > 0.0:
            keep = dropout_keep(seed_ref[0], b, qi, ki, (bq, bk), dropout_p)
            p_drop = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        else:
            p_drop = p
        dv_scr[:, :] += jax.lax.dot_general(
            p_drop, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
        ds = p * (dp - delta) * jnp.float32(scale)
        dk_scr[:, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, :, :] = dk_scr[:, :].astype(dk_ref.dtype)
        dv_ref[0, :, :] = dv_scr[:, :].astype(dv_ref.dtype)


def flash_attention_backward(q, k, v, o, lse, do, scale, causal,
                             block_q=256, block_k=256, interpret=False,
                             bias=None, segment_ids=None, num_heads=1,
                             dropout_p=0.0, dropout_seed=None):
    """All array args [BH, S, D] (lse [BH, S] fp32); returns (dq, dk, dv).

    `scale` is the softmax scale of the UNPADDED head dim (the caller pads D
    to a lane multiple; zero columns keep zero gradients automatically).
    bias is (B|1, H|1, Sq, Sk); segment_ids ((B, Sq), (B, Sk)); num_heads
    maps the flattened BH grid index back to (batch, head) for both.
    """
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    nq, nk = Sq // block_q, Sk // block_k
    H = num_heads
    has_bias = bias is not None
    has_seg = segment_ids is not None
    dropout_p = float(dropout_p)
    if dropout_p > 0.0:
        # dropout_keep packs (b, qi, ki) into ONE prng_seed word as
        # (b<<20)+(qi<<10)+ki: block indices at or above 2^10 would silently
        # alias seed bits and correlate keep masks across blocks. Grid dims
        # are static at trace time, so enforce the packing envelope here.
        assert nq < 1024 and nk < 1024, (
            f"flash-attention dropout PRNG seed packs q/k block indices into "
            f"10 bits each; got num_q_blocks={nq}, num_k_blocks={nk} "
            f"(seq_len/block size too large) — raise block_q/block_k so both "
            f"stay below 1024")

    # delta[b, i] = rowsum(dO ∘ O): one fused elementwise+reduce in XLA
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    # lane-broadcast the per-row stats so their blocks satisfy (8,128) tiling
    lse_b = jnp.broadcast_to(lse.astype(jnp.float32)[:, :, None],
                             (BH, Sq, LANES))
    delta_b = jnp.broadcast_to(delta[:, :, None], (BH, Sq, LANES))

    common = dict(causal=causal, bq=block_q, bk=block_k, scale=scale,
                  dropout_p=dropout_p, has_bias=has_bias, has_seg=has_seg)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    def shared_operands():
        """(operands, spec-builders) for the inputs both kernels share; each
        spec builder takes (bmap, imap, jmap) index functions where i indexes
        q-blocks and j indexes k-blocks."""
        ops, builders = [], []
        if dropout_p > 0.0:
            assert dropout_seed is not None
            ops.append(jnp.asarray(dropout_seed, jnp.int32).reshape(1))
            builders.append(lambda qm, km: pl.BlockSpec(
                memory_space=pltpu.SMEM))
        ops += [q, k, v, do]
        builders += [
            lambda qm, km: pl.BlockSpec((1, block_q, D),
                                        lambda *g: (g[0], qm(*g), 0)),
            lambda qm, km: pl.BlockSpec((1, block_k, D),
                                        lambda *g: (g[0], km(*g), 0)),
            lambda qm, km: pl.BlockSpec((1, block_k, D),
                                        lambda *g: (g[0], km(*g), 0)),
            lambda qm, km: pl.BlockSpec((1, block_q, D),
                                        lambda *g: (g[0], qm(*g), 0)),
        ]
        if has_bias:
            Bb, Hb = bias.shape[:2]
            ops.append(bias)
            builders.append(lambda qm, km: pl.BlockSpec(
                (1, 1, block_q, block_k),
                lambda *g: (0 if Bb == 1 else g[0] // H,
                            0 if Hb == 1 else g[0] % H, qm(*g), km(*g))))
        if has_seg:
            qs, ks = segment_ids
            B = qs.shape[0]
            ops.append(jax.lax.broadcast_in_dim(
                qs.astype(jnp.int32), (B, Sq, LANES), (0, 1)))
            builders.append(lambda qm, km: pl.BlockSpec(
                (1, block_q, LANES), lambda *g: (g[0] // H, qm(*g), 0)))
            ops.append(jax.lax.broadcast_in_dim(
                ks.astype(jnp.int32), (B, SUBLANES, Sk), (0, 2)))
            builders.append(lambda qm, km: pl.BlockSpec(
                (1, SUBLANES, block_k), lambda *g: (g[0] // H, 0, km(*g))))
        ops += [lse_b, delta_b]
        builders += [
            lambda qm, km: pl.BlockSpec((1, block_q, LANES),
                                        lambda *g: (g[0], qm(*g), 0)),
            lambda qm, km: pl.BlockSpec((1, block_q, LANES),
                                        lambda *g: (g[0], qm(*g), 0)),
        ]
        return ops, builders

    with jax.enable_x64(False):
        # dQ: grid (BH, q-block, k-block); k is the reduction (arbitrary) dim
        # (the scopes name the device ops: %flash_bwd_dq.N, %flash_bwd_dkv.N)
        ops, builders = shared_operands()
        qm, km = (lambda b, i, j: i), (lambda b, i, j: j)
        with jax.named_scope("flash_bwd_dq"):
            dq = pl.pallas_call(
                functools.partial(_dq_kernel, nk=nk, **common),
                out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
                grid=(BH, nq, nk),
                in_specs=[mk(qm, km) for mk in builders],
                out_specs=pl.BlockSpec((1, block_q, D),
                                       lambda b, i, j: (b, i, 0)),
                scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
                compiler_params=params,
                interpret=interpret,
            )(*ops)

        # dK/dV: grid (BH, k-block, q-block); q is the reduction dim
        ops, builders = shared_operands()
        qm, km = (lambda b, j, i: i), (lambda b, j, i: j)
        with jax.named_scope("flash_bwd_dkv"):
            dk, dv = pl.pallas_call(
                functools.partial(_dkv_kernel, nq=nq, **common),
                out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                           jax.ShapeDtypeStruct(v.shape, v.dtype)),
                grid=(BH, nk, nq),
                in_specs=[mk(qm, km) for mk in builders],
                out_specs=(
                    pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
                    pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
                ),
                scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                                pltpu.VMEM((block_k, D), jnp.float32)],
                compiler_params=params,
                interpret=interpret,
            )(*ops)
    return dq, dk, dv
