"""One-token attention of many query heads on ONE KV head through a page
table, on a TPU: the ``[B, 1]`` decode read of a multi-query layer whose pool
row is the one head's lanes (``[L, P, page_size, lanes]``).

The discipline of ``serving/paged_attention.py::_decode_kernel``: one
invocation sweeps, slot after slot, the pages each slot HOLDS (``pos //
page_size + 1`` of its table row), ``_PAGES`` pages a step, each fetched by
DMA from the pool in HBM (addressed ``(layer, table[b, j])`` off the
scalar-prefetched operands, no layer sliced out) into one half of a double
buffer while the other half is computed on. A table entry past a slot's last
live page is neither fetched nor computed; the gather read it replaces moves
the table's whole width, live or not, and writes it out again.

With one KV head the query heads are the free dimension of a product: a
step's scores are ``q [heads, lanes] @ K^T [lanes, pages x page_size]`` and
its context ``p @ V`` on the MXU, folded into an online softmax (m, l, acc
in VMEM). Keys past ``pos`` are masked to an exact zero weight (and their
rows of V to zeros: a buffer half may hold an earlier step's pages).

This copies ``_decode_kernel``'s sweep for one layout alone. When that
kernel groups query heads onto KV heads (the group the free dimension of
the product), it takes this one KV head too and this file goes."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32, BF16 = jnp.float32, jnp.bfloat16
_PAGES = 16              # pages a step carries; two steps' worth in flight
_HEADS = 16              # the query heads are padded to whole bf16 tiles


def _kernel(lay_ref, table_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
            v_buf, sems, m_ref, l_ref, acc_ref, *, page_size, table_pages):
    G = _PAGES
    lay = lay_ref[0]
    rows = G * page_size
    lanes = k_buf.shape[-1]

    def live(b):
        return jnp.minimum(pos_ref[b] // page_size + 1, table_pages)

    def steps(b):
        return (live(b) + G - 1) // G

    def fetch(b, j, half, act):
        def one(g, carry):
            phys = table_ref[b * table_pages + j * G + g]
            act(pltpu.make_async_copy(k_hbm.at[lay, phys], k_buf.at[half, g],
                                      sems.at[0, half]))
            act(pltpu.make_async_copy(v_hbm.at[lay, phys], v_buf.at[half, g],
                                      sems.at[1, half]))
            return carry
        jax.lax.fori_loop(0, jnp.minimum(live(b) - j * G, G), one, 0)

    def step(i, at):
        b, j = at
        half = i % 2
        done = j + 1 == steps(b)
        nxt = (jnp.where(done, b + 1, b), jnp.where(done, 0, j + 1))

        @pl.when(i + 1 < total)
        def _():
            fetch(*nxt, 1 - half, lambda dma: dma.start())

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        fetch(b, j, half, lambda dma: dma.wait())
        first, last = j * rows, pos_ref[b]
        k = k_buf[half].reshape(rows, lanes)
        v = v_buf[half].reshape(rows, lanes)
        key = first + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        s = jax.lax.dot_general(q_ref[b], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)  # [heads, rows]
        s = jnp.where(key <= last, s, -jnp.inf)
        m_prev = m_ref[:, :1]
        # a step's first key is live, so m_new is finite
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        row = first + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        v = jnp.where(row <= last, v, jnp.zeros_like(v))
        pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=F32)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + pv

        @pl.when(done)
        def _():
            o_ref[b] = acc_ref[...] / l_ref[:, :1]
        return nxt

    total = jax.lax.fori_loop(0, q_ref.shape[0],
                              lambda b, n: n + steps(b), 0)
    fetch(0, 0, 0, lambda dma: dma.start())
    jax.lax.fori_loop(0, total, step, (0, 0))


def paged_mqa_decode(q, kc, vc, layer, table, pos, *, page_size,
                     interpret=False):
    """q [B, heads, d] (a slot's one query; any float type), kc / vc the
    WHOLE pool ``[L, P, page_size, lanes]`` of one KV head (``d <= lanes``,
    the lanes past ``d`` zeros), read at the traced ``layer`` through
    ``table`` [B, pages] up to and with position ``pos`` [B]: the context
    [B, heads, d] float32 at scale ``d^-0.5``. The query is scaled and
    rounded to the pool's type (as a TPU's default-precision product rounds
    it), its heads padded to whole tiles."""
    B, H, d = q.shape
    lanes = kc.shape[-1]
    hp = -(-H // _HEADS) * _HEADS
    qp = jnp.zeros((B, hp, lanes), kc.dtype).at[:, :H, :d].set(
        (q.astype(F32) * d ** -0.5).astype(kc.dtype))
    whole = pl.BlockSpec((B, hp, lanes), lambda i, *prefetch: (0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    pages = (2, _PAGES, page_size, lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                    # layer, flat table, pos
        grid=(1,),
        in_specs=[whole, in_hbm, in_hbm],
        out_specs=whole,
        scratch_shapes=[
            pltpu.VMEM(pages, kc.dtype),          # k pages, two halves
            pltpu.VMEM(pages, vc.dtype),          # v pages
            pltpu.SemaphoreType.DMA((2, 2)),      # (k | v, half)
            pltpu.VMEM((hp, 128), F32),           # m (lane-broadcast)
            pltpu.VMEM((hp, 128), F32),           # l
            pltpu.VMEM((hp, lanes), F32),         # acc
        ])
    kernel = functools.partial(_kernel, page_size=page_size,
                               table_pages=table.shape[1])
    # Mosaic rejects x64-typed index math; the framework enables x64
    # globally, so pin 32-bit types for the kernel's trace
    with jax.enable_x64(False), jax.named_scope("paged_mqa_decode"):
        ctx = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, hp, lanes), F32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=64 << 20),
            interpret=interpret,
        )(jnp.asarray(layer, jnp.int32).reshape(1),
          table.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
          qp, kc, vc)
    return ctx[:, :H, :d]
