"""Tensor-parallel (mp-sharded) serving forward for the paged engine.

The training stack shards the mp axis Megatron-style: column-parallel
qkv/up, ROW-parallel out/down with a cross-chip reduction per block. A
reduction re-associates the contraction sum, so its result is only
numerically — not bitwise — equal to the single-chip matmul. Serving's
contract is stronger: engine output must be BITWISE identical to
single-chip ``generate_from_params`` for any admission order, greedy and
sampled. This module therefore runs a GATHER-ONLY schedule:

* every GEMM shards its OUTPUT dim (column-parallel with head-major qkv,
  ``out_w``/``down_w``/``head_w`` column-sharded too) and keeps the FULL
  contraction — each chip's block is bitwise equal to a column slice of
  the unsharded GEMM;
* the only collectives are all-gathers (pure data movement): the
  attention context and FFN activation before their full-contraction
  projections, each projection's output blocks, the feature-sharded
  embedding, and (vocab-divisible) the logits;
* the paged KV pool shards its HEAD axis — per chip ``[L, P, page,
  nh/mp, d]``, ~1/mp of the KV bytes — while the host-authoritative page
  table stays global: a page id addresses ``(chip, page)`` implicitly
  through the head shard, so the allocator, prefix cache and CoW
  machinery are untouched.

The exactness premium is bounded: per block the schedule moves one extra
activation-sized gather versus the two all-reduces of the Megatron
schedule, while per-chip GEMM FLOPs and KV-read bytes are 1/mp either
way — and per-token decode activations are tiny next to the weight and
KV traffic the sharding removes.

Three collective rungs (``FLAGS_comm_backend``, "mp=..."), all
bitwise-identical because the backend only moves bytes differently:

* ``gspmd`` (default) — whole ``lax.all_gather`` collectives, the
  schedule the partitioner would emit for this gather-only program;
* ``ring`` — each all-gather decomposes into mp-1 ``ppermute`` hops;
* ``fused`` — Pallas in-kernel rings: the column-parallel projections
  ride ``fused_gemm_ag`` (the GEMM's output blocks enter the ring
  straight from the epilogue, no HBM round trip) and the data gathers
  ride ``fused_ag_bucket``. CPU tier-1 runs the SAME kernels in
  interpret mode on the 8-virtual-device mesh
  (``dist_env.create_single_axis_mesh('mp', n)``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.generation import _final_ln
from ..models.gpt import ln_fp32
from ..ops.pallas_kernels.quant_gemm import lora_delta, compose_delta
from .paged_attention import (layer_ids, paged_attention_read,
                              paged_kv_scatter)

KV_SPEC = P(None, None, None, "mp", None)   # [L, P, page, nh@mp, d]


def serving_param_specs(mp_cfg, quant_weights=False):
    """Per-leaf PartitionSpecs of the serving layout (init_gpt_params
    structure, stacked [L, ...] blocks, HEAD-MAJOR qkv storage so a
    contiguous column shard is whole heads). Every matmul weight shards
    its OUTPUT dim; norms and the biases added after an output gather
    stay replicated. With ``quant_weights`` the int8/fp8 leaves carry
    per-output-channel ``<name>_s`` fp32 scales that shard WITH their
    channels — a chip's scale shard dequantizes exactly its own weight
    columns, which is what keeps mp quantized output bitwise identical
    to single-chip quantized output."""
    mpx = "mp"
    blocks = {
        "ln1_g": P(None, None), "ln1_b": P(None, None),
        "qkv_w": P(None, None, mpx), "qkv_b": P(None, mpx),
        "out_w": P(None, None, mpx), "out_b": P(None, None),
        "ln2_g": P(None, None), "ln2_b": P(None, None),
        "up_w": P(None, None, mpx), "up_b": P(None, mpx),
        "down_w": P(None, None, mpx), "down_b": P(None, None),
    }
    out = {
        "wte": P(None, mpx),            # feature-sharded: local lookup + AG
        "wpe": P(None, None),
        "lnf_g": P(None), "lnf_b": P(None),
        "head_w": P(None, mpx) if mp_cfg.shard_vocab else P(None, None),
        "blocks": blocks,
    }
    if quant_weights:
        for name in ("qkv_w", "out_w", "up_w", "down_w"):
            blocks[name + "_s"] = P(None, mpx)
        out["head_w_s"] = P(mpx) if mp_cfg.shard_vocab else P(None)
    return out


def shard_serving_params(params, config, mesh, mp_cfg, quant_spec=None):
    """Place a GPT param tree onto the serving mp layout. Accepts the
    LOGICAL qkv layout (permuted to head-major here) or params already in
    head-major storage (``config.qkv_head_major`` — what HybridTrainStep
    trains under the explicit mp schedule): those are device_put straight
    to the serving shardings, so an already-mp-sharded trained tree moves
    chip-to-chip without a host gather + re-shard round trip.

    ``quant_spec`` (serving/quant.py) quantizes the GEMM weights BEFORE
    placement: per-output-channel quantization is column-independent, so
    quantize-then-shard equals shard-then-quantize and the mp engine
    serves bit-identical int8/fp8 blocks to the single-chip engine's
    column slices. Pinned calibration scales (recorded on the logical
    layout) relabel head-major together with the qkv columns."""
    perm = None
    if not getattr(config, "qkv_head_major", False):
        from ..distributed.tp_overlap import (qkv_head_major_perm,
                                              to_qkv_head_major)
        params = {**params,
                  "blocks": to_qkv_head_major(params["blocks"],
                                              config.hidden_size,
                                              config.num_heads)}
        perm = qkv_head_major_perm(config.hidden_size, config.num_heads)
    quant_weights = quant_spec is not None and quant_spec.quantizes_weights
    if quant_weights:
        from . import quant as _sq
        if perm is None and getattr(config, "qkv_head_major", False):
            # already-head-major tree: pinned calibration scales (logical
            # layout) still need the column relabeling
            from ..distributed.tp_overlap import qkv_head_major_perm
            perm = qkv_head_major_perm(config.hidden_size,
                                       config.num_heads)
        params = _sq.quantize_params(params, config, quant_spec,
                                     qkv_perm=perm)
    specs = serving_param_specs(mp_cfg, quant_weights=quant_weights)
    return jax.tree_util.tree_map(
        lambda a, s: jax.device_put(jnp.asarray(a), NamedSharding(mesh, s)),
        params, specs)


# ---------------------------------------------------------------------------
# per-device collective helpers (inside the full-manual shard_map; every
# one is an exact gather — chip-order concat, no arithmetic)


def _ring_ag_last(x, axis, n):
    """ppermute ring all-gather along the LAST axis."""
    idx = lax.axis_index(axis)
    F = x.shape[-1]
    perm = [(i, (i + 1) % n) for i in range(n)]
    out = jnp.zeros(x.shape[:-1] + (n * F,), x.dtype)
    chunk = x
    for t in range(n):
        src = (idx - t) % n
        out = lax.dynamic_update_slice_in_dim(out, chunk, src * F,
                                              axis=x.ndim - 1)
        if t < n - 1:
            chunk = lax.ppermute(chunk, axis, perm)
    return out


def ag_last(x, axis, n, backend, meta):
    """Exact all-gather along the last axis: [..., F/n] -> [..., F] with
    blocks in chip (= logical) order."""
    if n == 1:
        return x
    if backend == "fused":
        from ..ops.pallas_kernels import fused_collectives as _fc
        out = _fc.fused_ag_bucket(meta, x.reshape(-1))       # [n, numel]
        out = out.reshape((n,) + x.shape)
        return jnp.moveaxis(out, 0, -2).reshape(
            x.shape[:-1] + (n * x.shape[-1],))
    if backend == "ring":
        return _ring_ag_last(x, axis, n)
    return lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True)


def gemm_ag(x, w, axis, n, backend, meta, scale=None, epilogue=None):
    """Column-parallel projection: full-contraction local block
    ``x @ w_shard`` + all-gather of the output blocks. Bitwise equal to
    ``x @ w_full`` on every rung (the fused rung's GEMM epilogue feeds
    the ring directly — ``fused_collectives.fused_gemm_ag``).

    ``scale`` (quantized serving): ``w`` is the raw int8/fp8 shard and
    ``scale`` its per-output-channel fp32 dequant shard — the dequant
    multiply rides the local GEMM epilogue (inside the Pallas kernel on
    the fused rung), so the mp engine never materializes an fp weight
    copy, and the scaled block equals the column slice of the single-chip
    quantized product bitwise.

    ``epilogue`` (adapter serving): element-wise function applied to the
    LOCAL output block BEFORE the gather — the per-slot LoRA delta
    compose. Element-wise maps commute with the pure-data-movement
    gather, so composing pre-gather equals composing on the full product:
    the bitwise contract survives. With an epilogue the fused rung routes
    its gather through ``fused_ag_bucket`` (the epilogue has to land
    between the GEMM and the ring, so the in-kernel fused_gemm_ag path
    is skipped for that projection — still an exact gather)."""
    if n == 1:
        if scale is not None:
            y = (x @ w.astype(x.dtype)) * scale.astype(x.dtype)
        else:
            y = x @ w
        return y if epilogue is None else epilogue(y)
    if backend == "fused" and epilogue is None:
        from ..ops.pallas_kernels import fused_collectives as _fc
        return _fc.fused_gemm_ag(meta, x, w, scale=scale)
    if scale is not None:
        y = (x @ w.astype(x.dtype)) * scale.astype(x.dtype)
    else:
        y = x @ w
    if epilogue is not None:
        y = epilogue(y)
    if backend == "fused":
        return ag_last(y, axis, n, backend, meta)
    if backend == "ring":
        return _ring_ag_last(y, axis, n)
    return lax.all_gather(y, axis, axis=y.ndim - 1, tiled=True)


# ---------------------------------------------------------------------------
# the per-device block + forward


def _local_proj(h, p, name):
    """Local column-block projection (output stays sharded): fp leaf, or
    int8/fp8 leaf + per-channel scale shard with the dequant multiply in
    the epilogue — the scaled block is bitwise the column slice of the
    single-chip quantized GEMM."""
    s = p.get(name + "_s")
    if s is None:
        return h @ p[name].astype(h.dtype)
    return (h @ p[name].astype(h.dtype)) * s.astype(h.dtype)


def _mp_block(p, h, kc, vc, l, table, pos, valid, nh, n, eps, page_size,
              use_kernel, axis, backend, meta, ksc_l=None, vsc_l=None,
              aid=None, ad_l=None):
    """Transformer block ``l`` on PER-CHIP shards: h [B, T, H] replicated,
    weights column-sharded (qkv head-major: the local contiguous shard is
    nh/n whole heads), the whole KV pool kc/vc [L, P, page, nh/n, d]
    holding the local heads only, written and read at layer l in place
    (paged_kv_scatter / paged_attention_read). Every op is
    either replicated elementwise math, a full-contraction GEMM block, a
    per-head attention (head subsets are bitwise-independent), or an
    exact gather — so the block output is bitwise identical to
    paged_attention._layer_paged on one chip, at EVERY dtype config
    (quantized weights dequantize in the epilogue against their own
    column-scale shard; the quantized KV pool's per-page scales are
    replicated and head-independent).

    Adapters (aid [B] + this layer's slab rows ``ad_l``): A slabs are
    replicated and B slabs shard with their OUTPUT channels, so each
    chip's delta is exactly the column slice of the single-chip delta
    (the rank-r intermediate ``x @ A[aid]`` is replicated-identical
    everywhere, full contraction). The delta composes onto the LOCAL
    base block before each gather — element-wise, so it commutes with
    the gather and the single-chip bitwise contract is untouched."""
    B, T, H = h.shape
    nh_l = nh // n
    d = H // nh

    def _delta_epi(x, name):
        """compose-epilogue for the local column block of ``name``, or
        None when the layer carries no delta for it."""
        if ad_l is None or name not in ad_l:
            return None
        A_l, B_l = ad_l[name]
        dlt = lora_delta(x, A_l, B_l, aid)
        return lambda y: compose_delta(y, dlt, aid)

    h1 = ln_fp32(h, p["ln1_g"], p["ln1_b"], eps)
    qkv = _local_proj(h1, p, "qkv_w") + p["qkv_b"].astype(h.dtype)
    qkv4 = qkv.reshape(B, T, nh_l, 3, d)        # head-major local columns
    q, k, v = qkv4[..., 0, :], qkv4[..., 1, :], qkv4[..., 2, :]

    kc, vc = paged_kv_scatter(kc, vc, l, k, v, table, pos, valid,
                              page_size, ksc_l, vsc_l)
    ctx = paged_attention_read(q, kc, vc, l, table, pos, page_size,
                               use_kernel, h.dtype, ksc_l,
                               vsc_l)                           # [B,T,nh_l,d]
    # gather the context heads (chip order == logical head order), then
    # the out projection keeps the FULL contraction against its column
    # shard — the one arrangement that is bitwise under sharding
    ctx_full = ag_last(ctx.reshape(B, T, nh_l * d), axis, n, backend, meta)
    out_s = p.get("out_w_s")
    attn = gemm_ag(ctx_full,
                   p["out_w"] if out_s is not None
                   else p["out_w"].astype(h.dtype),
                   axis, n, backend, meta, scale=out_s,
                   epilogue=_delta_epi(ctx_full, "out_w")) + \
        p["out_b"].astype(h.dtype)
    h = h + attn
    h2 = ln_fp32(h, p["ln2_g"], p["ln2_b"], eps)
    up = _local_proj(h2, p, "up_w")
    up_epi = _delta_epi(h2, "up_w")
    if up_epi is not None:
        up = up_epi(up)
    up = up + p["up_b"].astype(h.dtype)
    up = jax.nn.gelu(up, approximate=True)
    act = ag_last(up, axis, n, backend, meta)                   # [B, T, I]
    down_s = p.get("down_w_s")
    down = gemm_ag(act,
                   p["down_w"] if down_s is not None
                   else p["down_w"].astype(h.dtype),
                   axis, n, backend, meta, scale=down_s,
                   epilogue=_delta_epi(act, "down_w"))
    return h + down + p["down_b"].astype(h.dtype), kc, vc


def mp_paged_forward(params, config, ids, kc, vc, start, valid, table,
                     page_size, use_kernel, mesh, mp_cfg, kv_scales=None,
                     adapters=None):
    """Fused chunk/decode forward over the mp-sharded engine: same
    signature and semantics as ``paged_attention.paged_forward`` but with
    params/KV sharded over ``mesh``'s 1-D mp axis. Returns replicated
    logits [B, V] plus the updated head-sharded pools. ``kv_scales`` =
    (k_scale, v_scale) [L, P] per-page dequant scales of a quantized
    pool, replicated (a page's scale applies to every head shard).
    ``adapters`` = (aid [B], slabs) per-slot adapter operands: aid and
    the A slabs replicate; B slabs shard with their output channels
    (the quant-scale placement rule) so the per-chip delta lands on the
    local column block before the gather."""
    compute = jnp.dtype(config.compute_dtype or "float32")
    n, axis, backend = mp_cfg.n, mp_cfg.axis, mp_cfg.backend
    meta = mp_cfg.kernel_meta(mesh)
    nh = config.num_heads
    eps = config.layer_norm_epsilon
    quant_weights = "head_w_s" in params

    def device_fn(params, kc, vc, ids, start, valid, table, *extra):
        extra = list(extra)
        ksc, vsc = ((extra.pop(0), extra.pop(0)) if kv_scales is not None
                    else (None, None))
        aid_d, slabs_d = extra if adapters is not None else (None, None)
        B, T = ids.shape
        pos = start[:, None] + jnp.arange(T)[None, :]           # [B, T]
        x = ag_last(params["wte"].astype(compute)[ids], axis, n, backend,
                    meta) + \
            jnp.take(params["wpe"].astype(compute), pos, axis=0)

        def layer_fn(carry, xs):
            h, kc, vc = carry
            p_l, l, ksc_l, vsc_l, ad_l = xs
            return _mp_block(p_l, h, kc, vc, l, table, pos, valid, nh, n,
                             eps, page_size, use_kernel, axis, backend, meta,
                             ksc_l, vsc_l, aid_d, ad_l), None

        # the pools are the scan's carry, as in paged_forward
        (x, kc2, vc2), _ = jax.lax.scan(
            layer_fn, (x, kc, vc),
            (params["blocks"], layer_ids(params), ksc, vsc, slabs_d))
        idx = jnp.maximum(valid - 1, 0)
        xlast = jax.vmap(
            lambda xb, i: jax.lax.dynamic_slice_in_dim(xb, i, 1, axis=0))(
                x, idx)[:, 0]                                   # [B, H]
        xn = _final_ln(params, config, xlast)
        head_s = params.get("head_w_s")
        if mp_cfg.shard_vocab:
            logits = gemm_ag(xn,
                             params["head_w"] if head_s is not None
                             else params["head_w"].astype(jnp.float32),
                             axis, n, backend, meta, scale=head_s)
        elif head_s is not None:
            logits = (xn @ params["head_w"].astype(jnp.float32)) * \
                head_s.astype(jnp.float32)
        else:
            logits = xn @ params["head_w"].astype(jnp.float32)
        return logits, kc2, vc2

    in_specs = [serving_param_specs(mp_cfg, quant_weights), KV_SPEC,
                KV_SPEC, P(None, None), P(None), P(None), P(None, None)]
    args = [params, kc, vc, ids, start, valid, table]
    if kv_scales is not None:
        in_specs += [P(None, None), P(None, None)]
        args += [kv_scales[0], kv_scales[1]]
    if adapters is not None:
        aid_arr, slabs = adapters
        in_specs += [P(None),
                     {name: (P(None, None, None, None),
                             P(None, None, None, "mp"))
                      for name in slabs}]
        args += [aid_arr, slabs]
    mapped = jax.shard_map(
        device_fn, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(None, None), KV_SPEC, KV_SPEC), check_vma=False)
    return mapped(*args)


def replica_mesh(mp, devices=None):
    """A 1-D ('mp',) mesh over ``mp`` devices — the shape one serving
    replica (= one mp group) runs on. Does NOT touch the process-global
    mesh (a supervisor runs several replicas, each on its own devices)."""
    from jax.sharding import Mesh
    devices = list(jax.devices() if devices is None else devices)
    mp = int(mp)
    if mp > len(devices):
        raise ValueError(f"serving mp={mp} needs {mp} devices, only "
                         f"{len(devices)} available")
    return Mesh(np.array(devices[:mp]), ("mp",))
