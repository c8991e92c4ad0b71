"""Single-chip ZeRO-3-class FULL parameter offload for GPT training.

Capability target: the reference's group_sharded stage-3 with cpu offload
(ref: python/paddle/distributed/fleet/meta_parallel/sharding/
group_sharded_stage3.py:84) — parameters, gradients AND optimizer moments
live in host memory; the accelerator holds only the small embedding/head
leaves, ONE transformer layer's weights, and activations. That is what
lets a 6.7B (and, at bs=1, a 13B-class) GPT train on a single 16 GB chip
backed by host RAM.

TPU-native design (no CUDA-style manual prefetch hooks):

- Block params are stacked ``[L, ...]`` arrays in ``pinned_host`` memory.
  The forward is a ``lax.scan`` over layers whose body fetches layer ``l``
  with ``device_put(dynamic_index(host_param, l))`` — one layer resident
  at a time. ``jax.checkpoint`` around the body makes the backward refetch
  instead of keeping all layers alive.
- The BACKWARD needs no hand-written stash: the transpose of the fetch is
  ``device_put`` back to the source (host) sharding, and the scan transpose
  accumulates the per-layer cotangents into a host-resident ``[L, ...]``
  gradient via per-iteration dynamic-update-slices (the same sliced-DMA
  pattern framework/offload.py streams optimizer moments with).
  NOTE the block gradients are INTERNAL values of the jitted step — only
  params/opt-state appear in ``out_shardings`` — so their host residency is
  not pinned by any output annotation: it relies on XLA propagating the
  memory space of the ``device_put`` transpose into the scan-transpose
  accumulator. That implicit placement is exactly what the on-chip smoke
  (``tools_stage3_smoke.py``) validates: at 6.7B the ``[L, ...]`` gradient
  alone exceeds HBM, so a refactor that lets XLA hoist the accumulator
  chip-side fails immediately with an OOM instead of silently regressing.
  Keep that in mind before touching
  the ``device_put`` placement in ``hidden``'s scan body.
- The optimizer update for block params runs over host-resident p/g/m/v in
  one of two modes:
    * ``update="stream"`` — a per-layer loop round-trips each layer's
      p/g/m/v through HBM once (3D matrix leaves; the tiny 2D bias/norm
      leaves bulk-transfer, both because their total is ~0.4% of params
      and because [1, H] host-DMA slices trip the TPU sublane-tiling
      check — see framework/offload.py);
    * ``update="host"`` — jax host-offload compute (``compute_on``): the
      elementwise AdamW math executes on the host CPU next to the data,
      no DMA at all (preferred on TPU when the runtime supports it).
- Small leaves (wte/wpe/lnf/head) stay device-resident with device slots.

Single-device only by design: multi-chip scale-out uses the mesh paths
(HybridTrainStep ZeRO-3 shards params across chips instead of offloading).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .gpt import GPTConfig, gpt_block_fn
from .gpt_hybrid import init_gpt_params
from ..framework import offload as _ol


@dataclass
class Stage3OffloadTrainStep:
    config: GPTConfig
    optimizer: object
    param_dtype: object = jnp.bfloat16
    seed: int = 0
    update: str = "stream"        # "stream" (proven) or "host" (compute_on)
    offload_enabled: bool = True  # False = device-resident (CPU math tests)

    def __post_init__(self):
        if self.update not in ("stream", "host"):
            raise ValueError(f"update={self.update!r}")
        if getattr(self.optimizer, "_grad_clip", None) is not None:
            # global-norm clip needs every gradient before any update —
            # with host-resident grads that is a full extra 2x DMA sweep;
            # rely on Adam's per-parameter normalization instead
            raise ValueError(
                "Stage3OffloadTrainStep does not support grad_clip: the "
                "global norm would force a full gradient sweep through "
                "HBM; construct the optimizer without grad_clip")
        if not getattr(self.optimizer, "_elementwise_update", False):
            # same guard as framework/offload.streamed_apply_gradients:
            # per-layer slices change the math of norm/history updates
            raise ValueError(
                "Stage3OffloadTrainStep streams per-layer slices, which "
                "only equals the bulk update for elementwise optimizers "
                "(Adam/AdamW/SGD/...); Lamb/LARS/LBFGS are not supported")
        if self.update == "host" and self.offload_enabled \
                and not _ol.in_jit_transfers_supported():
            raise ValueError(
                "update='host' needs TPU in-jit memory transfers "
                "(compute_on host offload); use update='stream' here")
        if self.offload_enabled and not _ol.in_jit_transfers_supported():
            # silently training device-resident would defeat the class's
            # purpose (and OOM outright at the 6.7B scale it exists for)
            raise ValueError(
                "this backend has no in-jit memory-kind transfers, so "
                "stage-3 offload cannot run; pass offload_enabled=False "
                "for a device-resident (test) instance")
        self._real = bool(self.offload_enabled
                          and _ol.in_jit_transfers_supported())
        if self._real:
            # init the block weights HOST-side: init_gpt_params would
            # materialize all [L, ...] leaves in HBM first (13.4G at 6.7B
            # — an OOM before training starts). numpy generates straight
            # into host memory; only the small leaves touch the device.
            self.small, self.blocks = self._init_host(self.config,
                                                      self.seed,
                                                      self.param_dtype)
        else:
            key = jax.random.key(self.seed)
            params = init_gpt_params(self.config, key, self.param_dtype)
            self.blocks = params.pop("blocks")   # {name: [L, ...]}
            self.small = params                  # wte/wpe/lnf/head_w
        self.opt_small = self.optimizer.init_state(self.small)
        self.opt_blocks = self.optimizer.init_state(self.blocks)
        if self._real:
            host = _ol.with_memory_kind(None, "pinned_host")
            self.opt_blocks = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, host) if jnp.ndim(a) else a,
                self.opt_blocks)
        self._jitted = None

    @staticmethod
    def _init_host(config, seed, param_dtype):
        """Same shapes/distributions as init_gpt_params, but block leaves
        are generated with numpy and placed directly in pinned host
        memory — device transient is one SMALL leaf at most."""
        H, L, V = config.hidden_size, config.num_layers, config.vocab_size
        Ienv = config.ffn_mult * H
        std = config.initializer_range
        rng = np.random.default_rng(seed)
        # ml_dtypes gives numpy a native bfloat16, so the cast happens in
        # host memory — jnp casts would round-trip the (huge) f32 array
        # through the device
        import ml_dtypes  # noqa: F401  (registers 'bfloat16' with numpy)
        np_dtype = np.dtype(jnp.dtype(param_dtype).name)

        def norm_np(shape):
            return (rng.standard_normal(shape, dtype=np.float32)
                    * std).astype(np_dtype)

        host = _ol.with_memory_kind(None, "pinned_host")

        def to_h(a):
            return jax.device_put(np.asarray(a, np_dtype), host)

        blocks = {
            "ln1_g": to_h(np.ones((L, H), np.float32)),
            "ln1_b": to_h(np.zeros((L, H), np.float32)),
            "qkv_w": to_h(norm_np((L, H, 3 * H))),
            "qkv_b": to_h(np.zeros((L, 3 * H), np.float32)),
            "out_w": to_h(norm_np((L, H, H))),
            "out_b": to_h(np.zeros((L, H), np.float32)),
            "ln2_g": to_h(np.ones((L, H), np.float32)),
            "ln2_b": to_h(np.zeros((L, H), np.float32)),
            "up_w": to_h(norm_np((L, H, Ienv))),
            "up_b": to_h(np.zeros((L, Ienv), np.float32)),
            "down_w": to_h(norm_np((L, Ienv, H))),
            "down_b": to_h(np.zeros((L, H), np.float32)),
        }
        small = {
            "wte": jnp.asarray(norm_np((V, H))),
            "wpe": jnp.asarray(norm_np((config.max_seq_len, H))),
            "lnf_g": jnp.ones((H,), param_dtype),
            "lnf_b": jnp.zeros((H,), param_dtype),
            "head_w": jnp.asarray(norm_np((H, V))),
        }
        return small, blocks

    # -- compiled step -------------------------------------------------------
    def _build(self):
        config = self.config
        optimizer = self.optimizer
        compute = jnp.dtype(config.compute_dtype or "float32")
        block = gpt_block_fn(config)
        L = config.num_layers
        real = self._real
        dev = _ol.with_memory_kind(None, "device") if real else None
        host = _ol.with_memory_kind(None, "pinned_host") if real else None
        ident = lambda a: a  # noqa: E731
        to_dev = (lambda a: jax.device_put(a, dev)) if real else ident
        to_host = (lambda a: jax.device_put(a, host)) if real else ident

        def hidden(small, blocks, ids):
            B, S = ids.shape
            x = small["wte"].astype(compute)[ids] + \
                small["wpe"].astype(compute)[None, :S]
            # only 3D matrix leaves stream per layer: [1, H] host-DMA
            # slices of the 2D bias/norm leaves are the sublane-tiling
            # pattern the TPU dynamic-index emitter rejects (and their
            # BACKWARD would dynamic-update-slice host arrays the same
            # way — the observed compiler crash). The 2D leaves are
            # ~0.4% of params: bulk-fetch them once, index on device.
            big = {k: v for k, v in blocks.items() if v.ndim >= 3}
            small2d = {k: to_dev(v) for k, v in blocks.items()
                       if v.ndim < 3}

            def body(h, l):
                p_l = {k: to_dev(
                    jax.lax.dynamic_index_in_dim(v, l, 0, keepdims=False))
                    for k, v in big.items()}
                p_l.update({k: jax.lax.dynamic_index_in_dim(
                    v, l, 0, keepdims=False) for k, v in small2d.items()})
                return block(p_l, h), None

            x, _ = jax.lax.scan(jax.checkpoint(body), x, jnp.arange(L))
            from .gpt_hybrid import final_ln_fp32
            return final_ln_fp32(x, small["lnf_g"], small["lnf_b"],
                                 config.layer_norm_epsilon).astype(compute)

        def loss_fn(small, blocks, ids):
            from ..ops.fused_ce import fused_lm_loss
            h = hidden(small, blocks, ids)
            return fused_lm_loss(h, small["head_w"].astype(h.dtype), ids)

        small_mask = {n: not (n.endswith("_b") or "ln" in n or n == "wpe")
                      for n in self.small}
        block_mask = {n: not (n.endswith("_b") or "ln" in n)
                      for n in self.blocks}

        def step_fn(small, blocks, opt_small, opt_blocks, ids, lr):
            loss, (g_small, g_blocks) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(small, blocks, ids)
            new_small, new_opt_small = optimizer.apply_gradients(
                small, g_small, opt_small, lr, wd_mask=small_mask)
            if self.update == "host" and real:
                from jax.experimental.compute_on import compute_on

                def host_update(blocks, g_blocks, opt_blocks, lr):
                    return optimizer.apply_gradients(
                        blocks, g_blocks, opt_blocks, lr,
                        wd_mask=block_mask)
                with compute_on("device_host"):
                    new_blocks, new_opt_blocks = host_update(
                        blocks, g_blocks, opt_blocks, lr)
            else:
                # shared streamed loop; transfer_params routes the
                # host-resident p/g through the same per-slice fetch the
                # moments use (2D leaves bulk-transfer via its small path)
                new_blocks, new_opt_blocks = _ol.streamed_apply_gradients(
                    self.optimizer, blocks, g_blocks, opt_blocks, lr,
                    block_mask,
                    stacked={n for n, a in blocks.items() if a.ndim >= 3},
                    to_dev=to_dev if real else None,
                    to_host=to_host if real else None,
                    transfer_params=real)
            return loss, new_small, new_blocks, new_opt_small, new_opt_blocks

        kwargs = {"donate_argnums": (0, 1, 2, 3)}
        if real:
            hostish = lambda a: host if jnp.ndim(a) else None  # noqa: E731
            kwargs["out_shardings"] = (
                None,                                            # loss
                None,                                            # small
                jax.tree_util.tree_map(lambda a: host, self.blocks),
                None,                                            # opt_small
                jax.tree_util.tree_map(hostish, self.opt_blocks),
            )
        return jax.jit(step_fn, **kwargs)

    def __call__(self, ids):
        if self._jitted is None:
            self._jitted = self._build()
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        out = self._jitted(self.small, self.blocks, self.opt_small,
                           self.opt_blocks,
                           jnp.asarray(ids, jnp.int32), lr)
        loss, self.small, self.blocks, self.opt_small, self.opt_blocks = out
        return loss

    def num_params(self):
        leaves = (list(jax.tree_util.tree_leaves(self.small)) +
                  list(jax.tree_util.tree_leaves(self.blocks)))
        return int(sum(np.prod(l.shape) for l in leaves))
