"""The expert layer's grouped products on a TPU: rows sorted by expert
(``x`` [M, H], ``group_sizes`` [G] rows an expert) against the expert stacks
AS STORED (``[L, E, H, F]`` gate and up, ``[L, E, F, H]`` down), at the
traced ``layer`` and the experts ``lo .. lo + G``.

One grid step a (row tile, expert) pair that holds rows: MegaBlocks' dropless
form (jax's megablox kernel with its empty groups squeezed out), so an
expert that no row chose is never fetched. A step reads
the expert's whole ``[K, tn]`` block (``tn`` the whole width at every
published expert), so a group that spans two row tiles is fetched once. The
layer rides as a scalar-prefetch operand and the index maps address ``(layer,
lo + group)``: no layer or expert is sliced out of a stack (a Pallas call
cannot fuse a slice into its operand; XLA would copy it), the discipline of
``serving/paged_attention.py::_paged_decode_call`` for the KV pool.

Rows past the groups' sum are left unwritten: the caller drops them."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_BLOCK_BYTES = 8 << 20          # one stack's block; two are in flight


def _row_tile(m):
    return min(128, -(-m // 16) * 16)


def _col_tile(k, n, itemsize):
    tn = n
    while k * tn * itemsize > _BLOCK_BYTES and tn % 256 == 0:
        tn //= 2
    return tn


def _visits(group_sizes, m, tm):
    """The grid's metadata: each group's row offsets [G + 1], then for each
    grid step (at most ``m / tm + G - 1``) its group and its row tile, the
    groups in order and a group's tiles in order, so a tile that two groups
    share is visited twice in a row; and the number of steps."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    tiles = jnp.where(group_sizes > 0,
                      (ends - 1) // tm - starts // tm + 1, 0)
    last = jnp.cumsum(tiles)                # one past each group's steps
    step = jnp.arange(m // tm + G - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(last[None] <= step[:, None], axis=1), G - 1)
    tile = starts[group] // tm + step - (last - tiles)[group]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets, group.astype(jnp.int32),
            jnp.clip(tile, 0, m // tm - 1)), last[-1]


def _kernel(offsets, group_ids, m_tiles, layer, x_ref, *refs, tm, glu):
    """One (row tile, expert) pair: the tile's rows against the expert's
    block, stored where the rows are the expert's. ``glu``: two blocks,
    ``silu(x g) * (x u)`` with each product rounded to x's type first, as the
    products of the other backends are."""
    del layer
    *w_refs, o_ref = refs
    i = pl.program_id(1)
    g = group_ids[i]
    rows = m_tiles[i] * tm + jax.lax.broadcasted_iota(jnp.int32,
                                                      o_ref.shape, 0)
    mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
    x = x_ref[...]
    p = [jax.lax.dot(x, w[...].astype(x.dtype), preferred_element_type=F32)
         for w in w_refs]
    if glu:
        a, b = (v.astype(x.dtype).astype(F32) for v in p)
        p = [a * jax.nn.sigmoid(a) * b]
    o_ref[...] = jnp.where(mine, p[0].astype(o_ref.dtype), o_ref[...])


def _grouped(x, stacks, meta, layer, lo, tm, out_dtype, interpret):
    M, K = x.shape
    N = stacks[0].shape[-1]
    tn = _col_tile(K, N, stacks[0].dtype.itemsize)
    (offsets, group_ids, m_tiles), active = meta

    def rows(n, i, off, gid, mt, l):
        return mt[i], 0

    def expert(n, i, off, gid, mt, l):
        return l[0], lo + gid[i], 0, n

    def out(n, i, off, gid, mt, l):
        return mt[i], n

    size = x.dtype.itemsize
    vmem = 2 * (len(stacks) * K * tn * size + tm * K * size
                + tm * tn * jnp.dtype(out_dtype).itemsize) + (8 << 20)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        # at least one step: a dispatch whose rows all dropped (an idle
        # slot's warm-up) stores nothing and reads one block
        grid=(N // tn, jnp.maximum(active, 1)),
        in_specs=[pl.BlockSpec((tm, K), rows)]
        + [pl.BlockSpec((None, None, K, tn), expert)] * len(stacks),
        out_specs=pl.BlockSpec((tm, tn), out))
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, glu=len(stacks) == 2),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(vmem, 100 << 20)),
        interpret=interpret,
    )(offsets, group_ids, m_tiles, layer, x, *stacks)


def grouped_ffn(x, gate, up, down, group_sizes, layer, lo, interpret=False):
    """``silu(x gate_e) * (x up_e)`` then ``down_e`` for each row's expert
    ``e``: x [M, H] in the compute type, sorted by expert, ``group_sizes``
    [G] int32; the stacks ``[L, E, ...]`` read at ``(layer, lo + g)``.
    Returns [M, H] float32; rows past ``sum(group_sizes)`` are not written."""
    M = x.shape[0]
    tm = _row_tile(M)
    pad = -M % tm
    # Mosaic rejects x64-typed index math; the framework enables x64
    # globally, so pin 32-bit types for the metadata and the kernels' trace
    with jax.enable_x64(False):
        xp = jnp.pad(x, ((0, pad), (0, 0)))
        meta = _visits(group_sizes.astype(jnp.int32), M + pad, tm)
        layer = jnp.asarray(layer, jnp.int32).reshape(1)
        # the kernels' names on a device trace: %expert_glu.N, %expert_down.N
        with jax.named_scope("expert_glu"):
            act = _grouped(xp, (gate, up), meta, layer, lo, tm, x.dtype,
                           interpret)
        with jax.named_scope("expert_down"):
            y = _grouped(act, (down,), meta, layer, lo, tm, F32, interpret)
    return y[:M]
