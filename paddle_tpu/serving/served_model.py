"""The seam between ``serving.Engine`` and the model it serves.

The engine asks a model for two things and nothing else:

* its **cache geometry** (``CacheGeometry``): how many pool arrays a layer
  keeps, a token's row in each, the storage type. The engine allocates
  ``[layers, pages, page_size, *row]`` arrays from it, copies, snapshots and
  counts their pages and bytes, and never looks inside a row;
* its **paged forward**: ``forward(params, config, ids, pools, start, valid,
  table, page_size, ...) -> (logits [B, V], pools, stats)``, the fused
  chunk/decode step over those pools (the pools the layer scan's carry).
  ``stats`` is None or one small array that leaves the device with the
  tokens and goes to ``record(stats, kind, config)`` on the host.

A configuration object names its model through a ``served_model`` attribute;
one without it is the GPT family, whose seam is here. Scheduler, admission,
page table, prefix cache, copy-on-write, chunk ladder, sampling and the phase
clock are the engine's and shared by every model; what a model does not
support yet (``unsupported``: spec, quant, adapters, mp, kv_transfer)
the engine refuses by name at construction."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from ..models.generation import _cfg_key, _cfg_view, _logical_qkv
from .paged_attention import paged_forward, paged_kernel_supported, \
    pool_head_dim


@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    """A model's paged cache: ``names`` one per pool array a layer keeps,
    ``row`` a token's row in each as the model writes it (GPT: ``(nh, d)``
    twice; a latent cache: ``(576,)`` once), ``dtype`` its storage type."""
    names: tuple
    layers: int
    row: tuple
    dtype: str

    def pool_shape(self, num_pages, page_size):
        """On the device a row's last axis is whole lanes (``pool_head_dim``:
        row-major is then the TPU's default layout, PERF.md PR 26)."""
        return (self.layers, num_pages, page_size) + self.row[:-1] + \
            (pool_head_dim(self.row[-1]),)

    def logical(self, pool):
        """A host copy of a pool array (or of pages of it) at the model's
        own row width, contiguous: what snapshots, page payloads and the
        chaos hooks see."""
        return np.ascontiguousarray(np.asarray(pool)[..., :self.row[-1]])


class ServedModel:
    """Defaults of the seam; a model overrides what it has."""
    name = "model"
    unsupported = frozenset()

    def key(self, config):
        """Hashable key of the memoized step builders."""
        raise NotImplementedError

    def view(self, key):
        """The configuration as the forward reads it, from the key."""
        raise NotImplementedError

    def prepare(self, params, config):
        """The tree as the forward takes it."""
        return params

    def geometry(self, config):
        raise NotImplementedError

    def kernel_ok(self, config, mp, page_size):
        """Whether a Pallas decode kernel may take the [B, 1] read."""
        return False

    def forward(self, params, config, ids, pools, start, valid, table,
                page_size, **options):
        raise NotImplementedError

    def record(self, stats, kind, config):
        """The step's ``stats`` on the host, ``kind`` chunk | decode."""


class _GPTServed(ServedModel):
    name = "gpt"

    def key(self, config):
        return _cfg_key(config)

    def view(self, key):
        return _cfg_view(key)

    def prepare(self, params, config):
        # undo head-major qkv storage (sequence-parallel HybridTrainStep)
        # once at construction: single-chip decode splits qkv logically
        return _logical_qkv(params, config)

    def geometry(self, config):
        nh = config.num_heads
        return CacheGeometry(("kc", "vc"), config.num_layers,
                             (nh, config.hidden_size // nh),
                             str(jnp.dtype(config.compute_dtype or "float32")))

    def kernel_ok(self, config, mp, page_size):
        nh = config.num_heads
        return paged_kernel_supported(nh // mp, config.hidden_size // nh,
                                      page_size, why="serving engine")

    def forward(self, params, config, ids, pools, start, valid, table,
                page_size, use_kernel=False, kv_scales=None, wq_kernel=False,
                adapters=None, mp_key=None):
        kc, vc = pools
        if mp_key is None:
            logits, kc, vc = paged_forward(
                params, config, ids, kc, vc, start, valid, table, page_size,
                use_kernel, kv_scales=kv_scales, wq_kernel=wq_kernel,
                adapters=adapters)
        else:
            from .mp_forward import mp_paged_forward
            logits, kc, vc = mp_paged_forward(
                params, config, ids, kc, vc, start, valid, table, page_size,
                use_kernel, mp_key[0], mp_key[1], kv_scales=kv_scales,
                adapters=adapters)
        return logits, (kc, vc), None


GPT = _GPTServed()


def served_model(config):
    """The seam of the model that ``config`` configures."""
    return getattr(config, "served_model", GPT)
