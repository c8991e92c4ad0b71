"""Weight-only quantized GEMM Pallas kernel: int8/fp8 weight tiles
dequantized in the epilogue (fp32 accumulation, per-output-channel scale
multiply), so the full-precision weight never exists in HBM.

Routing mirrors ``paged_attention``'s kernel pattern: the kernel runs on
TPU behind ``FLAGS_serving_quant_kernel`` + a shape predicate
(``quant_gemm_supported``); everywhere else (and for unsupported shapes)
the SAME algebra runs as a jnp fallback —

    ``y = (x @ wq.astype(dt)) * s.astype(dt)``

— which XLA fuses the convert+scale of into the MXU matmul epilogue
anyway. Because the per-output-channel scale factors out of each column's
full contraction, this reassociation is the one arrangement that stays
bitwise identical under column sharding: the mp engine's per-chip block
``(x @ wq_shard) * s_shard`` IS the column slice of the single-chip
product, which is why the serving mp rungs keep their bitwise contract at
every quantized dtype config.

The KERNEL itself is the exception, exactly like the paged-decode kernel:
its k-tiled fp32 accumulation + fp32 scale epilogue is numerically
equivalent but NOT bitwise identical to the jnp epilogue (one rounding
instead of two under a bf16 compute dtype, tiled contraction order). It
routes on single-chip TPU engines only — disable
``FLAGS_serving_quant_kernel`` when auditing cross-mp-degree bitwise
parity at a quantized config on TPU (the jnp/fused-ring epilogues are
the bitwise-contract paths).
"""
from __future__ import annotations

import functools
import logging
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger("paddle_tpu.quant_gemm")

_lock = threading.Lock()
_trace_counts = {"quant_gemm": 0}


def trace_counts():
    with _lock:
        return dict(_trace_counts)


def reset_trace_counts():
    with _lock:
        for k in _trace_counts:
            _trace_counts[k] = 0


def quant_gemm_supported(R, K, F, why=""):
    """Routing predicate for the Pallas quant-GEMM kernel: TPU backend +
    Mosaic-friendly shapes (the jnp fallback serves everything else)."""
    reasons = []
    if jax.default_backend() != "tpu":
        reasons.append("backend is not TPU")
    if R % 8 != 0:
        reasons.append(f"rows {R} not a multiple of 8")
    if K % 128 != 0:
        reasons.append(f"contraction dim {K} not a multiple of 128")
    if F % 128 != 0:
        reasons.append(f"out dim {F} not a multiple of 128")
    if reasons:
        logger.info("quant gemm kernel fallback to jnp%s: %s",
                    f" ({why})" if why else "", "; ".join(reasons))
        return False
    return True


def _quant_gemm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk,
                       out_dtype):
    """Grid (F/bn, K/bk), k innermost: accumulate the int8/fp8 weight
    tile's GEMM in fp32 scratch; the LAST k-step's epilogue multiplies
    the per-output-channel scale and casts out — dequant never touches
    HBM."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = (acc_ref[:] * s_ref[0].astype(jnp.float32)
                      ).astype(out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def quant_gemm_kernel(x, wq, scale, block_n=128, block_k=128,
                      interpret=False):
    """x [R, K] fp, wq [K, F] int8/fp8, scale [F] fp32 -> [R, F] in
    x.dtype. fp32 accumulation; scale multiplied in the epilogue."""
    R, K = x.shape
    F = wq.shape[1]
    bn = min(block_n, F)
    bk = min(block_k, K)
    nk = K // bk

    # Mosaic rejects x64-typed index math (the index maps' literal 0 becomes
    # an i64 under the framework's global x64 flag): pin 32-bit types.
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_quant_gemm_kernel, nk=nk, out_dtype=x.dtype),
            grid=(F // bn, nk),
            in_specs=[
                pl.BlockSpec((R, bk), lambda f, k: (0, k)),
                pl.BlockSpec((bk, bn), lambda f, k: (k, f)),
                pl.BlockSpec((1, bn), lambda f, k: (0, f)),
            ],
            out_specs=pl.BlockSpec((R, bn), lambda f, k: (0, f)),
            out_shape=jax.ShapeDtypeStruct((R, F), x.dtype),
            scratch_shapes=[pltpu.VMEM((R, bn), jnp.float32)],
            interpret=interpret,
        )(x, wq, scale.reshape(1, F).astype(jnp.float32))


def quant_gemm(x, wq, scale, use_kernel=False, interpret=False):
    """Weight-only quantized projection ``x [..., K] @ wq [K, F]`` with
    the per-output-channel dequant scale [F] fused into the epilogue.
    ``use_kernel`` routes the Pallas kernel when the (static) shapes
    qualify; the jnp fallback is the identical algebra."""
    with _lock:
        _trace_counts["quant_gemm"] += 1
    lead = x.shape[:-1]
    K = x.shape[-1]
    F = wq.shape[-1]
    R = 1
    for s in lead:
        R *= int(s)
    if use_kernel and (interpret or quant_gemm_supported(R, K, F)):
        out = quant_gemm_kernel(x.reshape(R, K), wq, scale,
                                interpret=interpret)
        return out.reshape(lead + (F,))
    return (x @ wq.astype(x.dtype)) * scale.astype(x.dtype)


# ---------------------------------------------------------------------------
# LoRA-class adapter deltas: the other GEMM epilogue (serving/adapters.py)
#
# A quantized base projection and a full-precision low-rank delta COMPOSE:
# the base GEMM dequantizes in its epilogue (above) and the delta joins
# right after, before bias — so the adapted projection is
#
#     y = dequant(x @ wq) * s  (+)  (x @ A[aid]) @ B[aid]
#
# with (+) the masked compose below. The delta path is deliberately jnp:
# rank-r contractions are tiny (r ~ 8-64) and XLA fuses the pair of
# batched einsums into the surrounding epilogue on TPU.


def lora_delta(h, A_l, B_l, aid):
    """Per-slot low-rank delta for one layer: h [B, T, K] against the
    layer's adapter slabs A_l [cap, K, r] / B_l [cap, r, F], routed by the
    TRACED per-slot row ids aid [B] -> delta [B, T, F].

    Each batch row contracts only against ITS OWN adapter rows (a take
    then two batched einsums), so every row's result is bitwise
    independent of the rest of the batch — the property that lets a
    mixed-adapter engine batch stay bitwise-equal to per-adapter solo
    runs, exactly like the base matmuls. The LoRA ``alpha/r`` scale was
    folded into B at load time (AdapterRegistry.load) and rank padding
    is zero columns/rows, so this is scale-free and padding-exact."""
    Aa = jnp.take(A_l, aid, axis=0).astype(h.dtype)          # [B, K, r]
    Ba = jnp.take(B_l, aid, axis=0).astype(h.dtype)          # [B, r, F]
    xa = jnp.einsum("btk,bkr->btr", h, Aa)
    return jnp.einsum("btr,brf->btf", xa, Ba)


def compose_delta(base, delta, aid):
    """Join a delta onto the base projection output, per slot: rows with
    aid == 0 (base model) keep ``base`` BITWISE — a where-select, not
    ``base + 0.0``, because IEEE ``-0.0 + 0.0`` is ``+0.0`` and the
    mixed-batch parity contract requires base-model rows to be
    byte-identical to an adapters-off engine. Element-wise, so under mp
    it commutes with the output-channel all-gather: composing the local
    column block before the gather equals composing after it."""
    return jnp.where((aid > 0)[:, None, None], base + delta, base)
