"""Paged attention for the serving engine: transformer forward over a
block-paged KV pool ``[L, P, page_size, nh, d]`` read through a per-slot
page table.

Two implementations of the decode-attention read:

* **pure-jnp page gather** (default, every backend) — gather each slot's
  pages into virtual ``[B, S, nh, d]`` order and run exactly the math of
  ``models.generation._layer_cached``. Because appended masked keys
  contribute exact zeros to the softmax and context sums, the result is
  BITWISE identical to single-request ``generate_from_params`` — this is
  the tier-1 parity path.
* **Pallas TPU kernel** (``paged_decode_attention``) — one-token decode
  that sweeps the pages each slot HOLDS (``pos // page_size + 1`` of its
  table row, found through the scalar-prefetched table and pos), so only
  live pages move HBM->VMEM and its work follows the contexts, not the
  table's width (the gather path materializes the full virtual window).
  Online-softmax accumulation: numerically
  equivalent, not bitwise identical — gated behind
  ``FLAGS_serving_paged_kernel`` and a TPU-backend + shape predicate
  (``paged_kernel_supported``), mirroring the flash-attention routing.

The pool's last axis is ``pool_head_dim(d)``: head_dim padded up to the
TPU's 128 lanes, the pad zeros. The gather reads take ``[..., :d]``; the
kernel fetches whole rows, pad and all, and multiplies the pad by the zero
lanes of a query widened to the pool's lanes (GPT-3 2.7B: head_dim 80 in 128
lanes). The pool is never sliced, copied or
re-stacked by a step: it is the layer
scan's CARRY, each layer writes and reads it at ``[l, ...]`` (one scatter of
the window's rows; the kernel's index_map or the page gather's start
indices), and under the engine's donation XLA updates the buffer in place.

The fused step here is ALSO the chunked-prefill executable: every slot
processes a ``T``-token window at its own offset (``T=1`` pure decode;
``T=chunk`` while any prompt is prefilling), with per-slot ``start`` /
``valid`` / ``emit`` as traced operands. Padding lanes and inactive slots
scatter their K/V to physical page 0 (the trash page) and are never read
back unmasked.

Quantized serving (serving/quant.py, default-OFF): when the engine's kv
dtype is int8/fp8 the pool stores quantized values and ``kv_scales`` =
(k_scale, v_scale) ``[L, P]`` per-PAGE traced operands ride along —
writes quantize in ``paged_kv_scatter``, reads dequantize here (scores
are computed against the quantized keys and multiplied by the per-page
scale AFTER the dot, identically in every read branch, so all branches
— and every mp shard — stay bitwise consistent with each other at a
given dtype config). Quantized WEIGHT leaves carry a ``<name>_s``
per-output-channel scale companion consumed by ``quant_gemm`` (dequant
in the GEMM epilogue — no fp weight copy). With both dtypes at "bf16"
none of these operands exist and the math is byte-identical to the
unquantized engine.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.gpt import ln_fp32
from ..models.generation import _final_ln, _final_logits
from ..ops.pallas_kernels.quant_gemm import quant_gemm, lora_delta, \
    compose_delta

logger = logging.getLogger("paddle_tpu.paged_attention")


def _proj(h, p, name, wq_kernel=False):
    """One projection GEMM: full-precision ``h @ w`` when the leaf is fp,
    or the weight-only quantized GEMM (int8/fp8 leaf + per-output-channel
    ``<name>_s`` scale, dequant fused into the epilogue) when the engine
    quantized its weights."""
    s = p.get(name + "_s")
    if s is None:
        return h @ p[name].astype(h.dtype)
    return quant_gemm(h, p[name], s, use_kernel=wq_kernel)


def pool_head_dim(d):
    """The pool's last axis for head_dim ``d``: padded up to a multiple of
    the TPU's 128 lanes (GPT-3 2.7B's 80 -> 128; a multiple stays). A page
    row [nh, d] then is whole (8, 128) tiles, which is how the chip holds
    it while it computes in any case; what the pad buys is that row-major
    is also the device's DEFAULT layout for the array. Unpadded, a TPU
    lays bf16 [L, P, page, nh, 80] out with the PAGE axis minor-most and
    the step converts the whole pool on the way in and on the way out of
    every dispatch. (Pinning row-major with ``jax.experimental.layout``
    does not survive the persistent compile cache: PERF.md, PR 26.) The
    pad lanes hold zeros (``pad_lanes``). The gather reads below take
    ``[..., :d]``; the decode kernel fetches the whole row and meets the pad
    with the zero lanes of its widened query (``_paged_decode_call``)."""
    return -(-d // 128) * 128


def pad_lanes(x, like):
    """Rows ``x`` [..., d] widened with zeros to the last axis of the pool
    ``like``: a whole-row write is ONE in-place scatter on the chip, where
    a write of ``[..., :d]`` becomes a loop of row-sized updates."""
    pad = like.shape[-1] - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def paged_kernel_supported(nh, d, page_size, why="", itemsize=2):
    """Routing predicate for the Pallas paged-decode kernel (same pattern
    as ops.pallas_kernels.flash_supported): TPU backend + Mosaic-friendly
    shapes, logged fallback otherwise. Any head_dim is taken: a pool row is
    ``pool_head_dim(d)`` lanes, whole tiles whatever ``d``. What is checked
    of the rows is that the kernel's double buffer of them (``itemsize``
    bytes a value: the pool's storage dtype) fits its share of VMEM."""
    reasons = []
    if jax.default_backend() != "tpu":
        reasons.append("backend is not TPU")
    if nh % 8 != 0:
        reasons.append(f"num_heads {nh} not a multiple of 8")
    if page_size % 8 != 0:
        reasons.append(f"page_size {page_size} not a multiple of 8")
    # K and V, two halves each, of a step's pages [page, nh, lanes]
    buffers = (2 * 2 * _SWEEP_PAGES * page_size * nh * pool_head_dim(d)
               * itemsize)
    if buffers > _SWEEP_VMEM_BYTES:
        reasons.append(f"the page buffers ({buffers >> 20} MiB) pass "
                       f"{_SWEEP_VMEM_BYTES >> 20} MiB of VMEM")
    if reasons:
        logger.info("paged decode kernel fallback to jnp gather%s: %s",
                    f" ({why})" if why else "", "; ".join(reasons))
        return False
    return True


# ---------------------------------------------------------------------------
# Pallas TPU kernel: one-token decode through the page table


# pages one step of the sweep carries: their DMAs are in flight together
# (two steps' worth, double-buffered), so a slot of a few hundred positions
# is one to three steps and a page's latency hides behind seven others
# (4, 8 and 16 read within 5% on a v5e: the walk, not the fetch, is the
# bound; PERF.md section 6, PR 31)
_SWEEP_PAGES = 8
# what the K and V double buffers may take of the VMEM a Mosaic kernel is
# given (the query and output blocks [slots, nh, lanes] and the softmax
# state share the rest). Compiled for a described v5e at 16 slots: 12 MiB
# (96 heads x 128 lanes, bf16) are taken, 16 MiB (128 heads) refused; GPT-3
# 2.7B's 32 heads take 4 MiB
_SWEEP_VMEM_BYTES = 12 << 20


def _decode_kernel(*refs, page_size, scale, quant, table_pages):
    """One invocation sweeps, slot after slot, the pages each slot HOLDS:
    ``pos[b] // page_size + 1`` of the ``table_pages`` entries of its table
    row, in steps of up to ``_SWEEP_PAGES`` pages. The pools stay whole in
    HBM; a step's live pages come to VMEM by one DMA a page, addressed
    (layer, table[b, j]) off the scalar-prefetched operands, into one half
    of a double buffer while the other half is computed on, and the step
    after a slot's last is the next slot's first, so only the call's first
    fetch is exposed. A table entry past a slot's last live page is never
    looked at: no fetch (not of trash page 0 either), no compute.

    Two widths: the pool's ``lanes`` (its last axis, whole 128-lane tiles)
    and the model's head_dim ``d <= lanes``. The kernel sees only lanes:
    q, the page buffers, the accumulator and the output block are ``lanes``
    wide, the query's lanes past ``d`` are zeros (so a score is the d-lane
    dot product whatever the pool's pad holds) and ``scale`` is the
    caller's ``1/sqrt(d)``.

    A page is walked one key position at a time on the VPU: position s is
    a native [nh, lanes] tile, its score column is a lane reduction and its
    context contribution a broadcast multiply-add, folded into the online
    softmax state (m, l, acc in VMEM scratch) once a page; the last live
    page is masked by position. No dot_general: the per-head contraction
    "hd,shd->hs" has its batch dim in the middle of the page and no free
    lhs dim, which Mosaic's dot lowering refuses
    (TPU_DotDimensionNumbersAttr 'lhs_non_contracting_dims', jax 0.9.0).

    ``quant``: the pool holds int8/fp8 values and the per-PAGE dequant
    scales arrive as two more scalar-prefetch operands — scores scale
    after the q.k reduction, v contributions inside the ctx accumulation,
    so the fp K/V bytes never exist in HBM."""
    if quant:
        (lay_ref, table_ref, pos_ref, ksc_ref, vsc_ref, q_ref, k_hbm, v_hbm,
         o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref) = refs
    else:
        (lay_ref, table_ref, pos_ref, q_ref, k_hbm, v_hbm,
         o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref) = refs
    G = _SWEEP_PAGES
    lay = lay_ref[0]

    def live_pages(b):
        return jnp.minimum(pos_ref[b] // page_size + 1, table_pages)

    def steps(b):
        return (live_pages(b) + G - 1) // G

    def phys_page(b, j, g):
        return table_ref[b * table_pages + j * G + g]

    def for_live_pages(b, j, fn):
        """fn(g) for the pages g of slot b's step j that are live, in a
        loop (the body is traced once, whatever ``_SWEEP_PAGES``); the
        table is read for those alone (a dead entry's index may lie past
        the row)."""
        def body(g, carry):
            fn(g)
            return carry
        jax.lax.fori_loop(0, jnp.minimum(live_pages(b) - j * G, G), body, 0)

    def fetch(b, j, half, act):
        def one(g):
            phys = phys_page(b, j, g)
            for hbm, buf, sem in ((k_hbm, k_buf, sems.at[0, half]),
                                  (v_hbm, v_buf, sems.at[1, half])):
                act(pltpu.make_async_copy(hbm.at[lay, phys],
                                          buf.at[half, g], sem))
        for_live_pages(b, j, one)

    def walk(b, j, half, q, g):
        k_scale = scale
        if quant:
            phys = phys_page(b, j, g)
            k_scale = scale * ksc_ref[phys]
        first = (j * G + g) * page_size
        last = pos_ref[b]
        cols = []                                            # ps x [nh, 1]
        for s in range(page_size):
            c = jnp.sum(q * k_buf[half, g, s].astype(jnp.float32), axis=-1,
                        keepdims=True) * k_scale
            cols.append(jnp.where(first + s <= last, c, -jnp.inf))

        m_prev = m_ref[:, :1]                                # [nh, 1]
        m_new = m_prev
        for c in cols:
            m_new = jnp.maximum(m_new, c)
        # a walked page's first position is live, so m_new is finite and
        # exp(-inf - m_new) is an exact 0: for a slot's first page (m_prev)
        # and for the masked tail of its last (c)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[:, :1]
        pv = jnp.zeros(acc_ref.shape, jnp.float32)           # [nh, lanes]
        for s, c in enumerate(cols):
            p = jnp.exp(c - m_new)
            l_new = l_new + p
            pv = pv + p * v_buf[half, g, s].astype(jnp.float32)
        if quant:
            pv = pv * vsc_ref[phys]
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[:] = acc_ref[:] * alpha + pv

    def step(i, at):
        b, j = at
        half = i % 2
        slot_done = j + 1 == steps(b)
        nxt = (jnp.where(slot_done, b + 1, b), jnp.where(slot_done, 0, j + 1))

        @pl.when(i + 1 < total)
        def _():
            fetch(*nxt, 1 - half, lambda dma: dma.start())

        @pl.when(j == 0)
        def _():
            m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        fetch(b, j, half, lambda dma: dma.wait())
        q = q_ref[b].astype(jnp.float32)                     # [nh, lanes]
        for_live_pages(b, j, functools.partial(walk, b, j, half, q))

        @pl.when(slot_done)
        def _():
            o_ref[b] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)
        return nxt

    total = jax.lax.fori_loop(0, q_ref.shape[0],
                              lambda b, n: n + steps(b), 0)
    fetch(0, 0, 0, lambda dma: dma.start())
    jax.lax.fori_loop(0, total, step, (0, 0))


def _paged_decode_call(q, kc, vc, layer, table, pos, scales, page_size,
                       interpret):
    """pallas_call shared by the fp and quantized-pool entry points. kc/vc
    are the WHOLE pool [L, P, page_size, nh, lanes], left in HBM, and
    ``layer`` a traced scalar: it rides as the first scalar-prefetch operand
    and the kernel's page fetches address (layer, phys page), so no layer of
    the pool is sliced out (and copied) for the kernel. ``layer=None`` takes
    one layer's [P, page_size, nh, lanes] (a free leading axis, layer 0).
    ``scales`` is () or that layer's (ksc_l, vsc_l) [P] fp32, prefetched
    to SMEM after the flat table and pos.

    Two widths, both read off the operands: the model's head_dim ``d`` is
    q's last axis, the pool's ``lanes`` is kc's (``pool_head_dim(d)``).
    Where they differ q is widened with zeros to the lanes, every block and
    scratch buffer is ``lanes`` wide, the scores keep ``1/sqrt(d)`` and the
    output's pad lanes are cut; where they are one (d a multiple of 128)
    neither the pad nor the cut is traced."""
    if layer is None:
        kc, vc, layer = kc[None], vc[None], 0
    B, nh, d = q.shape
    lanes = kc.shape[-1]
    if lanes != d:
        q = pad_lanes(q, kc)
    whole = pl.BlockSpec((B, nh, lanes), lambda i, *prefetch: (0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    pages = (2, _SWEEP_PAGES, page_size, nh, lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # layer, flat table, pos[, scales]
        num_scalar_prefetch=3 + len(scales),
        grid=(1,),
        in_specs=[whole, in_hbm, in_hbm],
        out_specs=whole,
        scratch_shapes=[
            pltpu.VMEM(pages, kc.dtype),             # k pages, two halves
            pltpu.VMEM(pages, vc.dtype),             # v pages
            pltpu.SemaphoreType.DMA((2, 2)),         # (k | v, half)
            pltpu.VMEM((nh, 128), jnp.float32),      # m (lane-broadcast)
            pltpu.VMEM((nh, 128), jnp.float32),      # l
            pltpu.VMEM((nh, lanes), jnp.float32),    # acc
        ],
    )
    kernel = functools.partial(_decode_kernel, page_size=page_size,
                               scale=1.0 / (d ** 0.5), quant=bool(scales),
                               table_pages=table.shape[1])
    # Mosaic rejects x64-typed index math; the framework enables x64 globally
    # for dtype parity, so pin 32-bit types inside the kernel trace.
    with jax.enable_x64(False):
        ctx = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, nh, lanes), jnp.float32),
            interpret=interpret,
        )(jnp.asarray(layer, jnp.int32).reshape(1),
          table.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
          *(sc.astype(jnp.float32) for sc in scales),
          q.astype(jnp.float32), kc, vc)
    return ctx if lanes == d else ctx[..., :d]


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def paged_decode_attention_q(q, kc, vc, table, pos, ksc_l, vsc_l, *,
                             page_size, layer=None, interpret=False):
    """Quantized-pool one-token paged attention: like
    ``paged_decode_attention`` plus the layer's per-page dequant scales
    ksc_l/vsc_l [P] (fp32) prefetched to SMEM and applied inside the page
    sweep."""
    return _paged_decode_call(q, kc, vc, layer, table, pos, (ksc_l, vsc_l),
                              page_size, interpret)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def paged_decode_attention(q, kc, vc, table, pos, *, page_size, layer=None,
                           interpret=False):
    """One-token paged attention: q [B, nh, d] (fp32), table [B, MP],
    pos [B] -> ctx [B, nh, d] fp32. kc/vc are the whole pool
    [L, P, page_size, nh, pool_head_dim(d)] read at the traced scalar
    ``layer``, or with ``layer=None`` one layer's [P, page_size, nh,
    pool_head_dim(d)]. Unmapped table entries are 0 (trash page) and
    masked by pos."""
    return _paged_decode_call(q, kc, vc, layer, table, pos, (), page_size,
                              interpret)


# ---------------------------------------------------------------------------
# fused step forward (jnp gather path; kernel spliced in for T=1 on TPU)


def _quantize_kv(x, sc, dtype):
    """Quantize one K/V window [B, T, nh', d] with its per-position page
    scale sc [B, T] into the pool's storage dtype (int8 round+clip; fp8
    saturating cast). Head-independent, so any head subset (the mp
    engine's shard) quantizes bitwise-identically to the full write."""
    scaled = x.astype(jnp.float32) / sc[:, :, None, None]
    if dtype == jnp.int8:
        return jnp.clip(jnp.round(scaled), -128, 127).astype(jnp.int8)
    info = jnp.finfo(dtype)
    return jnp.clip(scaled, float(info.min), float(info.max)).astype(dtype)


def _write_slots(table, pos, writable, page_size, ring=False):
    """(phys, off) [B, T] of the pool rows the window positions pos map
    to through the table; lanes that are not ``writable`` route to trash
    page 0. With ``ring`` the table row is a ring: position p lives in
    logical page ``(p // page_size) mod`` the row's width."""
    li = pos // page_size
    li = li % table.shape[1] if ring else jnp.minimum(li, table.shape[1] - 1)
    phys = jnp.where(writable, jnp.take_along_axis(table, li, axis=1), 0)
    return phys, pos % page_size


def paged_kv_scatter(kc, vc, l, k, v, table, pos, valid, page_size,
                     ksc_l=None, vsc_l=None, ring=False):
    """Scatter one window's K/V [B, T, nh', d] into layer ``l`` (traced
    scalar) of the WHOLE paged pool kc/vc [L, P, page_size, nh',
    pool_head_dim(d)] through the slot->page table: logical page -> physical; lanes past
    valid[b] (and whole inactive slots) write to trash page 0. One
    scatter at (l, phys, off) into the carried pool, so under donation
    XLA updates the buffer in place and the B x T rows are the only bytes
    written. ``nh'`` is whichever head count the caller holds — all heads
    single-chip, the local shard under mp (the table is
    head-independent). With a quantized pool the layer's per-page scales
    ksc_l/vsc_l [P] quantize the write in place (trash page 0 keeps scale
    1.0; its garbage is never read unmasked). ``nh'`` may also be a model's
    KV heads, fewer than its query heads. ``ring``: the table row is a
    window group's ring (``_write_slots``)."""
    phys, off = _write_slots(table, pos, jnp.arange(pos.shape[1])[None, :]
                             < valid[:, None], page_size, ring)
    if ksc_l is not None:
        k = _quantize_kv(k, ksc_l[phys], kc.dtype)
        v = _quantize_kv(v, vsc_l[phys], vc.dtype)
    kc = kc.at[l, phys, off].set(pad_lanes(k.astype(kc.dtype), kc))
    vc = vc.at[l, phys, off].set(pad_lanes(v.astype(vc.dtype), vc))
    return kc, vc


def ring_key_positions(table_pages, last_pos, page_size):
    """The absolute position [B, table_pages * page_size] that each entry
    of a slot's ring holds once the window ending at ``last_pos`` [B] is
    written: ring page r holds the newest page congruent to r that is not
    past the frontier page. An entry never written reads as a position
    below 0 or past the window's end (its page's older lap, not yet
    overwritten, is labelled with the new lap's positions), so the mask by
    absolute position gives it an exact zero."""
    front = (last_pos // page_size)[:, None]                    # [B, 1]
    r = jnp.arange(table_pages)[None, :]
    page = front - (front - r) % table_pages                    # [B, R]
    return (page[:, :, None] * page_size
            + jnp.arange(page_size)[None, None, :]).reshape(
                last_pos.shape[0], -1)


def window_mask(pos_q, pos_k, window=None):
    """Whether the query at absolute position pos_q [B, T] sees the key at
    pos_k [B | 1, S]: ``0 <= j <= i`` and, with ``window``, ``i - j <
    window``. [B, T, S]."""
    behind = pos_q[:, :, None] - pos_k[:, None, :]
    mask = (behind >= 0) & (pos_k >= 0)[:, None, :]
    return mask if window is None else mask & (behind < window)


def grouped_attend(q, k, v, mask, out_dtype):
    """Attention of q [B, T, nh, d] over k and v [B, S, nkv, d] under mask
    [B, T, S], float32 inside: query head j reads KV head ``j // (nh /
    nkv)`` (the heads of a group contiguous). Masked keys contribute exact
    zeros."""
    B, T, nh, d = q.shape
    nkv = k.shape[2]
    qg = q.reshape(B, T, nkv, nh // nkv, d)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / (d ** 0.5)
    scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgts,bskd->btkgd", probs, v.astype(jnp.float32))
    return ctx.reshape(B, T, nh, d).astype(out_dtype)


def _grouped_read(q, kc, vc, l, table, pos, page_size, out_dtype, window):
    """The gather read for a pool of fewer KV heads than query heads and,
    with ``window`` (positions), of a ring: the table row is gathered whole
    (the ring's pages, never the context's), and a key counts by its
    ABSOLUTE position (``window_mask``)."""
    B, _, _, d = q.shape
    nkv = kc.shape[3]
    S = table.shape[1] * page_size
    kv_k = kc[l, table][..., :d].reshape(B, S, nkv, d)
    kv_v = vc[l, table][..., :d].reshape(B, S, nkv, d)
    if window is None:
        key_pos = jnp.arange(S)[None, :]
    else:
        key_pos = ring_key_positions(table.shape[1], pos[:, -1], page_size)
    return grouped_attend(q, kv_k, kv_v, window_mask(pos, key_pos, window),
                          out_dtype)


def paged_attention_read(q, kc, vc, l, table, pos, page_size, use_kernel,
                         out_dtype, ksc_l=None, vsc_l=None, window=None):
    """Paged attention read: q [B, T, nh', d] against layer ``l`` (traced
    scalar) of the WHOLE pool kc/vc [L, P, page_size, nh',
    pool_head_dim(d)] through the table; returns ctx [B, T, nh', d] in ``out_dtype``. The layer is
    addressed inside the consuming operation (the kernel's index_map, the
    page gather's start indices), never sliced out first. Every head's
    math is independent and mirrors generation._layer_cached
    exactly, so any head SUBSET (the mp engine's per-chip shard) is
    bitwise identical to the same heads of the full computation.

    Quantized pool (the layer's ksc_l/vsc_l [P] per-page scales present):
    scores are computed against the QUANTIZED keys and multiplied by the
    key page's scale AFTER the dot — every position of a page shares one
    scale, so the multiply factors out of the contraction and both read
    branches below compute bit-identical scores; V dequantizes after its
    gather. The per-dtype exactness contract (mp == single-chip,
    order/restore invariance) rides on this branch-consistency.

    A pool of fewer KV heads than q has, or a ``window`` (the table row is
    then a ring), takes ``_grouped_read``: no kernel, no quantized pool."""
    B, T, nh, d = q.shape
    MP = table.shape[1]
    if kc.shape[3] != nh or window is not None:
        assert not use_kernel and ksc_l is None
        return _grouped_read(q, kc, vc, l, table, pos, page_size, out_dtype,
                             window)

    if use_kernel and T == 1:
        if ksc_l is not None:
            return paged_decode_attention_q(
                q[:, 0].astype(jnp.float32), kc, vc, table, pos[:, 0],
                ksc_l, vsc_l, page_size=page_size,
                layer=l)[:, None].astype(out_dtype)
        return paged_decode_attention(
            q[:, 0].astype(jnp.float32), kc, vc, table, pos[:, 0],
            page_size=page_size,
            layer=l)[:, None].astype(out_dtype)                 # [B,1,nh,d]
    S = MP * page_size
    P = kc.shape[1]
    if T == 1 and 2 * P * page_size <= B * S:
        # decode on an UNDERSUBSCRIBED pool (physical pages well below
        # the sum of virtual windows — the memory-equal serving
        # regime): score the query against the pool once and gather
        # only the tiny score rows into virtual order. Each score is
        # the same q-dot-k over d either way, so this is bitwise
        # identical to scoring gathered keys while reading far fewer
        # key bytes (measured ~2.8x faster at P*ps ~ B*S/6; the
        # gather branch wins when P*ps ~ B*S, hence the static 2x
        # shape guard).
        s_all = jnp.einsum("bthd,pshd->bhtps", q.astype(jnp.float32),
                           kc[l, ..., :d].astype(jnp.float32)) / (d ** 0.5)
        scores = jax.vmap(lambda sa, tb: sa[:, :, tb])(
            s_all, table).reshape(B, nh, T, S)
    else:
        # chunk prefill (pool-wide scoring is FLOP-heavy for T
        # queries) and amply-sized pools: gather the key window
        kv_k = kc[l, table][..., :d].reshape(B, S, nh, d)
        scores = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                            kv_k.astype(jnp.float32)) / (d ** 0.5)
    if ksc_l is not None:
        # per-position key scale in virtual order [B, S]: the dequant
        # multiply lands AFTER the dot in both branches identically
        k_sc = jnp.repeat(ksc_l[table], page_size, axis=1)      # [B, S]
        scores = scores * k_sc[:, None, None, :]
    kv_v = vc[l, table][..., :d].reshape(B, S, nh, d).astype(jnp.float32)
    if vsc_l is not None:
        v_sc = jnp.repeat(vsc_l[table], page_size, axis=1)      # [B, S]
        kv_v = kv_v * v_sc[:, :, None, None]
    # absolute causal mask; masked keys (incl. trash/unmapped reads)
    # contribute exact zeros, preserving bitwise parity with the
    # contiguous layouts
    mask = jnp.arange(S)[None, None, :] <= pos[:, :, None]      # [B, T, S]
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", probs,
                      kv_v).astype(out_dtype)


# ---------------------------------------------------------------------------
# a latent cache: one row a token a layer, no head axis (models/xing4.py)


def latent_scatter(pool, l, rows, table, pos, valid, page_size):
    """``paged_kv_scatter`` for a pool of one array and no head axis
    ``[L, P, page_size, lanes]``: the window's rows [B, T, row] go to layer
    ``l`` (traced scalar) at (phys, off) through the table, lanes past
    valid[b] to trash page 0; one in-place scatter into the carried pool."""
    phys, off = _write_slots(table, pos, jnp.arange(pos.shape[1])[None, :]
                             < valid[:, None], page_size)
    return pool.at[l, phys, off].set(pad_lanes(rows.astype(pool.dtype), pool))


def latent_window(pool, l, table, row):
    """Layer ``l``'s rows of every slot in virtual order [B, S, row],
    gathered through the table; the layer is addressed by the gather."""
    B, MP = table.shape
    return pool[l, table][..., :row].reshape(B, MP * pool.shape[2], row)


def layer_ids(params):
    """The layer scan's index operand, [L] int32 over the tree's blocks (a
    layer-truncated draft tree walks the pool's leading layers)."""
    return jnp.arange(params["blocks"]["qkv_b"].shape[0], dtype=jnp.int32)


def _adapted_proj(h, p, name, wq_kernel, aid, ad_l):
    """``_proj`` plus the per-slot LoRA delta epilogue: when this layer's
    adapter slab covers ``name`` the low-rank delta joins the base GEMM
    output (before bias) through the masked compose — aid==0 rows keep
    the base product bitwise. qkv_w is never in ``ad_l`` by construction
    (AdapterRegistry forbids it), keeping the delta GEMM out of the
    attention inner loop; prefix pages of ADAPTED requests still depend
    on the delta bits through the residual stream, which is why the
    engine salts their prefix-cache keys (engine._prefix_salt)."""
    base = _proj(h, p, name, wq_kernel)
    if ad_l is None or name not in ad_l:
        return base
    A_l, B_l = ad_l[name]
    return compose_delta(base, lora_delta(h, A_l, B_l, aid), aid)


def _qkv_proj(h, p, wq_kernel=False):
    """The fused q, k, v product of one block plus its bias, h [B, T, H] to
    [B, T, 3H]. A full-precision stack comes as the engine stores it,
    ``qkv_wt`` [3H, H] a layer (``generation._stored_qkv``), and is read
    as stored; a quantized one, or a tree the engine did not prepare,
    takes ``_proj``."""
    wt = p.get("qkv_wt")
    if wt is None:
        qkv = _proj(h, p, "qkv_w", wq_kernel)
    else:
        qkv = jnp.einsum("bth,nh->btn", h, wt.astype(h.dtype))
    return qkv + p["qkv_b"].astype(h.dtype)


def _qkv(p, h, nh, eps, wq_kernel):
    """The first norm and the fused q, k, v projection of one block over
    h [B, T, H]: three [B, T, nh, d]. Under ``pt_attn_qkv`` on a device
    trace, like every stage of the paged step under a ``pt_*`` scope of
    its own (``profiler.device_time`` sums device seconds by them)."""
    B, T, H = h.shape
    with jax.named_scope("pt_attn_qkv"):
        h1 = ln_fp32(h, p["ln1_g"], p["ln1_b"], eps)
        qkv = _qkv_proj(h1, p, wq_kernel)
        q, k, v = jnp.split(qkv.reshape(B, T, 3, nh, H // nh), 3, axis=2)
        return q[:, :, 0], k[:, :, 0], v[:, :, 0]


def _layer_paged(p, h, kc, vc, l, table, pos, valid, nh, eps, page_size,
                 use_kernel, ksc_l=None, vsc_l=None, wq_kernel=False,
                 aid=None, ad_l=None):
    """Transformer block ``l`` over h [B, T, H] where each batch row is a
    serving slot processing the token window at absolute positions
    pos[b, :] (valid[b] of them real). K/V are scattered into layer l of
    the whole pool kc/vc through the page table (padding lanes -> trash
    page 0), which comes back updated; attention reads the gathered
    virtual window with the absolute causal mask. Math mirrors
    generation._layer_cached exactly, so a slot's
    stream is bitwise identical to single-request decode. Quantized
    engines route the GEMMs through ``_proj`` (epilogue dequant) and the
    KV writes/reads through the per-page scales. With adapters enabled,
    aid [B] + this layer's slab rows ``ad_l`` route each slot's low-rank
    delta into the out/up/down projection epilogues (qkv itself stays
    un-adapted)."""
    B, T, H = h.shape

    q, k, v = _qkv(p, h, nh, eps, wq_kernel)
    with jax.named_scope("pt_kv_write"):
        kc, vc = paged_kv_scatter(kc, vc, l, k, v, table, pos, valid,
                                  page_size, ksc_l, vsc_l)
    with jax.named_scope("pt_attn_read"):
        ctx = paged_attention_read(q, kc, vc, l, table, pos, page_size,
                                   use_kernel, h.dtype, ksc_l, vsc_l)

    with jax.named_scope("pt_attn_out"):
        attn = _adapted_proj(ctx.reshape(B, T, H), p, "out_w", wq_kernel,
                             aid, ad_l) + p["out_b"].astype(h.dtype)
        h = h + attn
    with jax.named_scope("pt_ffn"):
        h2 = ln_fp32(h, p["ln2_g"], p["ln2_b"], eps)
        up = _adapted_proj(h2, p, "up_w", wq_kernel, aid, ad_l) + \
            p["up_b"].astype(h.dtype)
        up = jax.nn.gelu(up, approximate=True)
        h = h + _adapted_proj(up, p, "down_w", wq_kernel, aid, ad_l) + \
            p["down_b"].astype(h.dtype)
    return h, kc, vc


def paged_forward(params, config, ids, kc, vc, start, valid, table,
                  page_size, use_kernel=False, kv_scales=None,
                  wq_kernel=False, adapters=None):
    """Fused chunk/decode forward: ids [B, T] is each slot's token window at
    absolute positions start[b]..start[b]+T-1 (valid[b] real). Returns
    logits at each slot's position valid[b]-1 ([B, V]) plus the updated
    paged pools [L, P, page_size, nh, d]. The pools are the layer scan's
    CARRY and every layer addresses them as [l, ...]: no pool-shaped array
    is sliced out as ``xs`` or re-stacked as ``ys``, so with kc/vc donated
    the step writes B x T rows a layer and nothing else. ``kv_scales`` =
    (k_scale, v_scale) [L, P] traced per-page dequant scales when the pool
    is quantized; ``wq_kernel`` routes quantized weight GEMMs through the
    Pallas quant kernel (TPU). ``adapters`` = (aid [B], slabs {target:
    (A [L, cap, K, r], B [L, cap, r, F])}) traced per-slot adapter rows —
    the slabs ride the layer scan alongside the block weights and the
    per-slot delta joins the projection epilogues (adapters.py)."""
    compute = jnp.dtype(config.compute_dtype or "float32")
    B, T = ids.shape
    pos = start[:, None] + jnp.arange(T)[None, :]               # [B, T]
    with jax.named_scope("pt_embed"):
        x = params["wte"].astype(compute)[ids] + \
            jnp.take(params["wpe"].astype(compute), pos, axis=0)
    nh = config.num_heads
    ksc, vsc = kv_scales if kv_scales is not None else (None, None)
    aid, slabs = adapters if adapters is not None else (None, None)

    def layer_fn(carry, xs):
        h, kc, vc = carry
        p_l, l, ksc_l, vsc_l, ad_l = xs
        return _layer_paged(p_l, h, kc, vc, l, table, pos, valid, nh,
                            config.layer_norm_epsilon, page_size, use_kernel,
                            ksc_l, vsc_l, wq_kernel, aid, ad_l), None

    # a None (no quantized pool, no adapters) is an empty pytree to scan.
    # What the scan itself does on the device (each layer's weights sliced
    # out of their stacks and copied into the layout their product wants,
    # the loop's carry) is pt_layers'; a stage keeps its own, innermost
    with jax.named_scope("pt_layers"):
        (x, kc, vc), _ = jax.lax.scan(
            layer_fn, (x, kc, vc),
            (params["blocks"], layer_ids(params), ksc, vsc, slabs))
    with jax.named_scope("pt_head"):
        idx = jnp.maximum(valid - 1, 0)
        xlast = jax.vmap(
            lambda xb, i: jax.lax.dynamic_slice_in_dim(xb, i, 1, axis=0))(
                x, idx)[:, 0]                                   # [B, H]
        return _head_logits(params, config, xlast, wq_kernel), kc, vc


# ---------------------------------------------------------------------------
# speculative decoding: verify forward (+ KV rewind) and the draft forward


def _layer_verify(p, h, kc, vc, l, table, pos, valid, nh, eps, page_size,
                  use_kernel, ksc_l=None, vsc_l=None, wq_kernel=False):
    """``_layer_paged`` with the attention read decomposed PER LANE: each
    of the T window lanes reads the pool at the [B, 1] shape — the exact
    dot/softmax/contraction shapes of the plain engine's one-token decode
    — instead of one [B, T] read. The [B, T] contraction over the virtual
    window is mathematically identical but NOT bitwise (the backend may
    block a T-row GEMM differently than T=1's matvec), and the verify
    pass's whole contract is that an accepted lane's KV bytes and logits
    are bit-for-bit what the plain engine would have produced. T is the
    static k+1, so the unrolled loop stays a small fixed cost."""
    B, T, H = h.shape

    q, k, v = _qkv(p, h, nh, eps, wq_kernel)
    with jax.named_scope("pt_kv_write"):
        kc, vc = paged_kv_scatter(kc, vc, l, k, v, table, pos, valid,
                                  page_size, ksc_l, vsc_l)
    with jax.named_scope("pt_attn_read"):
        ctx = jnp.concatenate(
            [paged_attention_read(q[:, t:t + 1], kc, vc, l, table,
                                  pos[:, t:t + 1], page_size, use_kernel,
                                  h.dtype, ksc_l, vsc_l)
             for t in range(T)], axis=1)

    with jax.named_scope("pt_attn_out"):
        attn = _proj(ctx.reshape(B, T, H), p, "out_w", wq_kernel) + \
            p["out_b"].astype(h.dtype)
        h = h + attn
    with jax.named_scope("pt_ffn"):
        h2 = ln_fp32(h, p["ln2_g"], p["ln2_b"], eps)
        up = _proj(h2, p, "up_w", wq_kernel) + p["up_b"].astype(h.dtype)
        up = jax.nn.gelu(up, approximate=True)
        h = h + _proj(up, p, "down_w", wq_kernel) + \
            p["down_b"].astype(h.dtype)
    return h, kc, vc


def _head_logits(params, config, x, wq_kernel=False):
    """LM-head logits over arbitrary leading dims, routing through the
    quantized head when the tree carries one."""
    if "head_w_s" in params:
        xn = _final_ln(params, config, x)
        return quant_gemm(xn, params["head_w"], params["head_w_s"],
                          use_kernel=wq_kernel)
    return _final_logits(params, config, x)


def paged_verify_forward(params, config, ids, kc, vc, start, valid, table,
                         page_size, use_kernel=False, kv_scales=None,
                         wq_kernel=False):
    """Speculative VERIFY forward: exactly ``paged_forward``'s math over
    the window ids [B, T] (T = k+1: the last emitted token + k draft
    proposals), except that (a) logits come back for EVERY lane
    ([B, T, V] — the accept scan needs all of them) and (b) the pre-write
    STORAGE-dtype pool bytes of every written position are gathered per
    layer BEFORE the scatter and returned ([L, B, T, nh, d] saved_k/
    saved_v), so ``paged_kv_rewind`` can restore rejected lanes without a
    second forward. Lane 0's logits are bitwise identical to the plain
    fused step's logits for the same slot state: the scatter-then-read
    order, the absolute causal mask and the per-row LN/GEMM math are all
    unchanged, and appended masked lanes contribute exact zeros."""
    compute = jnp.dtype(config.compute_dtype or "float32")
    B, T = ids.shape
    pos = start[:, None] + jnp.arange(T)[None, :]               # [B, T]
    x = params["wte"].astype(compute)[ids] + \
        jnp.take(params["wpe"].astype(compute), pos, axis=0)
    nh = config.num_heads
    ksc, vsc = kv_scales if kv_scales is not None else (None, None)
    # the same phys/off routing as paged_kv_scatter: padding lanes and
    # inactive slots resolve to trash page 0, whose pre-write bytes are
    # saved (and later rewritten) harmlessly
    phys, off = _write_slots(table, pos, jnp.arange(T)[None, :]
                             < valid[:, None], page_size)

    def layer_fn(carry, xs):
        h, kc, vc = carry
        p_l, l, ksc_l, vsc_l = xs
        saved_k = kc[l, phys, off]           # [B, T, nh, d] storage dtype
        saved_v = vc[l, phys, off]
        carry = _layer_verify(p_l, h, kc, vc, l, table, pos, valid, nh,
                              config.layer_norm_epsilon, page_size,
                              use_kernel, ksc_l, vsc_l, wq_kernel)
        return carry, (saved_k, saved_v)

    with jax.named_scope("pt_layers"):
        (x, kc, vc), (saved_k, saved_v) = jax.lax.scan(
            layer_fn, (x, kc, vc),
            (params["blocks"], layer_ids(params), ksc, vsc))
    with jax.named_scope("pt_head"):
        logits = _head_logits(params, config, x, wq_kernel)     # [B, T, V]
    return logits, kc, vc, saved_k, saved_v


def paged_kv_rewind(kc, vc, saved_k, saved_v, table, start, valid, n_emit,
                    page_size):
    """Restore the pool bytes the verify pass wrote past each slot's
    accepted length: lanes n_emit[b] <= i < valid[b] get their pre-write
    STORAGE-dtype bytes back (already-quantized bytes on a quantized
    pool — the restore bypasses re-quantization by construction, and the
    host-side per-page scales were never touched). After this the pool is
    byte-identical to a plain engine that decoded n_emit[b] tokens —
    except physical page 0, the trash page, which both engines treat as
    write-only garbage. Non-restored lanes route to page 0 exactly like
    ``paged_kv_scatter``'s padding lanes."""
    T = saved_k.shape[2]
    pos = start[:, None] + jnp.arange(T)[None, :]               # [B, T]
    lane = jnp.arange(T)[None, :]
    phys, off = _write_slots(
        table, pos, (lane >= n_emit[:, None]) & (lane < valid[:, None]),
        page_size)
    # one scatter over every layer at once: saved_* are [L, B, T, nh, d]
    return kc.at[:, phys, off].set(saved_k), vc.at[:, phys, off].set(saved_v)


def _draft_layer(p_l, h, kc, vc, l, sk_l, sv_l, table, base_pos, i, nh,
                 eps, page_size, ksc_l, vsc_l):
    """Draft transformer block ``l`` at T=1: the current draft token reads
    layer l of the REAL paged pool kc/vc [L, P, page_size, nh, d]
    (strictly below base_pos — positions at/past it hold stale rewound
    bytes) jointly with the in-flight draft K/V
    sidecar (lanes 0..i), one concatenated softmax. The pool is never
    written: draft K/V live only in the sidecar, so rejected drafts need
    zero rewind."""
    B, T, H = h.shape
    d = H // nh
    kmax = sk_l.shape[1]

    h1 = ln_fp32(h, p_l["ln1_g"], p_l["ln1_b"], eps)
    qkv = _qkv_proj(h1, p_l)
    q, kx, vx = jnp.split(qkv.reshape(B, 1, 3, nh, d), 3, axis=2)
    q, kx, vx = q[:, :, 0], kx[:, :, 0], vx[:, :, 0]
    sk_l = sk_l.at[:, i].set(kx[:, 0])
    sv_l = sv_l.at[:, i].set(vx[:, 0])

    S = table.shape[1] * page_size
    kv_k = kc[l, table][..., :d].reshape(B, S, nh, d)
    sc_pool = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                         kv_k.astype(jnp.float32)) / (d ** 0.5)
    if ksc_l is not None:
        k_sc = jnp.repeat(ksc_l[table], page_size, axis=1)      # [B, S]
        sc_pool = sc_pool * k_sc[:, None, None, :]
    sc_side = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                         sk_l.astype(jnp.float32)) / (d ** 0.5)
    pool_mask = (jnp.arange(S)[None, :] <
                 base_pos[:, None])[:, None, None, :]           # strict
    side_mask = (jnp.arange(kmax) <= i)[None, None, None, :]
    scores = jnp.concatenate(
        [jnp.where(pool_mask, sc_pool, -jnp.inf),
         jnp.where(side_mask, sc_side, -jnp.inf)], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1)
    kv_v = vc[l, table][..., :d].reshape(B, S, nh, d).astype(jnp.float32)
    if vsc_l is not None:
        v_sc = jnp.repeat(vsc_l[table], page_size, axis=1)      # [B, S]
        kv_v = kv_v * v_sc[:, :, None, None]
    vals = jnp.concatenate([kv_v, sv_l.astype(jnp.float32)], axis=1)
    ctx = jnp.einsum("bhts,bshd->bthd", probs, vals).astype(h.dtype)

    attn = _proj(ctx.reshape(B, 1, H), p_l, "out_w") + \
        p_l["out_b"].astype(h.dtype)
    h = h + attn
    h2 = ln_fp32(h, p_l["ln2_g"], p_l["ln2_b"], eps)
    up = _proj(h2, p_l, "up_w") + p_l["up_b"].astype(h.dtype)
    up = jax.nn.gelu(up, approximate=True)
    return h + _proj(up, p_l, "down_w") + \
        p_l["down_b"].astype(h.dtype), sk_l, sv_l


def paged_draft_forward(params, config, tok, kc, vc, pos, table, page_size,
                        k, kv_scales=None):
    """Speculative DRAFT forward: greedily roll the draft model ``k``
    tokens ahead of each slot's last emitted token ``tok`` [B] at
    absolute position ``pos`` [B], reading the engine's paged pool
    READ-ONLY and carrying the draft's own K/V in a compute-dtype sidecar
    [Ld, B, k, nh, d]. ``params`` may be a quantized and/or
    layer-truncated tree (Ld = its block count; the pool's leading layers
    line up because shallow drafts keep the FIRST blocks). Proposals are
    always greedy — the verify pass owns sampling and the PRNG stream.
    Returns proposals [B, k] int32."""
    compute = jnp.dtype(config.compute_dtype or "float32")
    B = tok.shape[0]
    nh = config.num_heads
    d = config.hidden_size // nh
    layers = layer_ids(params)                                 # [Ld]
    Ld = layers.shape[0]
    ksc, vsc = kv_scales if kv_scales is not None else (None, None)
    kscd = ksc[:Ld] if ksc is not None else None
    vscd = vsc[:Ld] if vsc is not None else None
    sk0 = jnp.zeros((Ld, B, k, nh, d), compute)
    sv0 = jnp.zeros((Ld, B, k, nh, d), compute)

    def step_fn(carry, i):
        cur, sk, sv = carry
        p = pos + i
        # jnp.take clips OOB positions (a slot about to hit max_seq_len)
        x = params["wte"].astype(compute)[cur][:, None] + \
            jnp.take(params["wpe"].astype(compute), p, axis=0)[:, None]

        def layer_fn(h, xs):
            p_l, l, sk_l, sv_l, ksc_l, vsc_l = xs
            h, sk_l, sv_l = _draft_layer(p_l, h, kc, vc, l, sk_l, sv_l,
                                         table, pos, i, nh,
                                         config.layer_norm_epsilon,
                                         page_size, ksc_l, vsc_l)
            return h, (sk_l, sv_l)

        # the pool is read-only here: closed over whole, indexed [l, table]
        x, (sk, sv) = jax.lax.scan(
            layer_fn, x, (params["blocks"], layers, sk, sv, kscd, vscd))
        logits = _head_logits(params, config, x[:, 0])          # [B, V]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (nxt, sk, sv), nxt

    _, props = jax.lax.scan(step_fn, (tok, sk0, sv0), jnp.arange(k))
    return props.T                                              # [B, k]
