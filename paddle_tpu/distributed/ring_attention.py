"""Ring attention — sequence/context parallelism for long sequences.

Capability target: the reference's long-sequence path (sequence parallelism in
fleet + fused attention). TPU-native design follows Ring Attention (Liu et al.)
over the ICI ring: Q stays resident, K/V blocks rotate via `ppermute`, and the
softmax is accumulated online (flash-attention style, fp32 accumulators), so
sequence length scales linearly with the number of chips at O(S/n) memory per
chip and the K/V transfer overlaps compute around the ring.

Also provides the all-to-all variant (DeepSpeed-Ulysses style): resharding
[B, S/n, H, D] -> [B, S, H/n, D] with one `all_to_all` before and after plain
attention — cheaper when H >= n and sequences fit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from . import env

_NEG_INF = -1e30


def _block_attn(q, k, v, m_prev, l_prev, acc_prev, block_mask):
    """One online-softmax block update. q:[B,Sq,H,D] k,v:[B,Sk,H,D];
    block_mask broadcastable to [B,H,Sq,Sk] (True=keep) or None."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * (d ** -0.5)
    if block_mask is not None:
        s = jnp.where(block_mask, s, _NEG_INF)
    m_cur = jnp.max(s, axis=-1)                      # [B,H,Sq]
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows: exp(-inf - -inf) -> use where
    p = jnp.exp(s - m_new[..., None])
    if block_mask is not None:
        p = jnp.where(block_mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    acc_new = acc_prev * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def ring_attention_spmd(q, k, v, *, axis_name="sp", causal=True):
    """Inside shard_map manual over `axis_name`. q,k,v: [B, S_local, H, D]
    (local sequence chunk). Returns [B, S_local, H, D]."""
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Sl, H, D = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    m = jnp.full((B, H, Sl), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, Sl), jnp.float32)
    acc = jnp.zeros((B, H, Sl, D), jnp.float32)

    k_cur, v_cur = k, v
    for step in range(n):
        src = (my - step) % n  # which chunk k_cur/v_cur belong to
        if causal:
            # chunk-level causality: key chunk must not be after query chunk
            q_pos = my * Sl + jnp.arange(Sl)              # global query positions
            k_pos = src * Sl + jnp.arange(Sl)
            mask = (k_pos[None, :] <= q_pos[:, None])     # [Sq, Sk]
            mask = mask[None, None]                        # [1,1,Sq,Sk]
        else:
            mask = None
        m, l, acc = _block_attn(q, k_cur, v_cur, m, l, acc, mask)
        if step != n - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)

    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def ring_attention(q, k, v, mesh=None, axis_name="sp", causal=True):
    """Host-side wrapper: q,k,v [B, S, H, D] logically; sequence dim sharded
    over `axis_name`. Works with GSPMD-auto other axes."""
    mesh = mesh or env.get_mesh()
    from ..tensor_impl import Tensor, as_tensor_data
    qa, ka, va = (as_tensor_data(t) for t in (q, k, v))
    spec = P(None, axis_name, None, None)
    mapped = jax.shard_map(
        functools.partial(ring_attention_spmd, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=frozenset({axis_name}))
    out = mapped(qa, ka, va)
    return Tensor(out) if isinstance(q, Tensor) else out


def ulysses_attention_spmd(q, k, v, *, axis_name="sp", causal=True):
    """All-to-all sequence parallelism: exchange seq-shard for head-shard,
    run full-sequence attention per head group, exchange back."""
    n = lax.axis_size(axis_name)
    B, Sl, H, D = q.shape
    assert H % n == 0, f"heads {H} not divisible by sp degree {n}"

    def seq2head(x):
        # [B, S/n, H, D] -> [B, S, H/n, D]
        x = x.reshape(B, Sl, n, H // n, D)
        x = lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=False)
        return x.reshape(B, Sl * n, H // n, D)

    def head2seq(x):
        S = x.shape[1]
        x = x.reshape(B, n, S // n, H // n, D)
        x = lax.all_to_all(x, axis_name, split_axis=1, concat_axis=3, tiled=False)
        return x.reshape(B, S // n, H, D)

    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    S = qh.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh).astype(jnp.float32) * (D ** -0.5)
    if causal:
        cm = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(cm[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vh.astype(jnp.float32)).astype(q.dtype)
    return head2seq(out)


def ulysses_attention(q, k, v, mesh=None, axis_name="sp", causal=True):
    mesh = mesh or env.get_mesh()
    from ..tensor_impl import Tensor, as_tensor_data
    qa, ka, va = (as_tensor_data(t) for t in (q, k, v))
    spec = P(None, axis_name, None, None)
    mapped = jax.shard_map(
        functools.partial(ulysses_attention_spmd, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=frozenset({axis_name}))
    out = mapped(qa, ka, va)
    return Tensor(out) if isinstance(q, Tensor) else out
