"""The seam between ``serving.Engine`` and the model it serves.

The engine asks a model for two things and nothing else:

* its **cache geometry** (``CacheGeometry``): one or more GROUPS of layers
  (``CacheGroup``), each with the pool arrays a layer of it keeps, a token's
  row in each and, where the group's layers attend to a window, the window.
  For every group the engine allocates ``[layers, pages, page_size, *row]``
  arrays and keeps a page table and an allocator of its own
  (``PagedKVPool``); it copies, snapshots and counts their pages and bytes
  and never looks inside a row. A group with no window is mapped for a
  request's whole lifetime. A window group's slot is a RING of
  ``ring_pages(window, widest chunk, page_size)`` pages whatever the
  context: position p lives in logical page ``(p // page_size) mod`` that
  many. A request is admitted when every group can hold it;
* its **paged forward**: ``forward(params, config, ids, pools, start, valid,
  table, page_size, ...) -> (logits [B, V], pools, stats)``, the fused
  chunk/decode step over those pools (the pools the layer scans' carry).
  ``pools`` is every group's arrays in the geometry's order, flat; ``table``
  is the one group's table ``[B, pages]`` or, with several groups, a tuple
  of them in the groups' order. ``stats`` is None or one small array that
  leaves the device with the tokens and goes to ``record(stats, kind,
  config)`` on the host.

A configuration object names its model through a ``served_model`` attribute;
one without it is the GPT family, whose seam is here. Scheduler, admission,
page tables, chunk ladder, sampling and the phase clock are the engine's and
shared by every model; what a model does not support yet (``unsupported``:
spec, quant, adapters, mp, kv_transfer, prefix_cache) the engine refuses by
name at construction. Prefix sharing and copy-on-write work on the first
group alone, so a model with a window group lists ``prefix_cache``: a ring
page holds different positions over a request's life."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from ..models.generation import _cfg_key, _cfg_view, _logical_qkv
from .paged_attention import paged_forward, paged_kernel_supported, \
    pool_head_dim


@dataclasses.dataclass(frozen=True)
class CacheGroup:
    """The layers of a model that share one page table: ``names`` one per
    pool array a layer keeps, ``row`` a token's row in each as the model
    writes it (GPT: ``(nh, d)`` twice; grouped heads: ``(kv heads, d)``; a
    latent cache: ``(576,)`` once), ``window`` None or the positions a
    layer of the group attends to (its slot is then a ring)."""
    names: tuple
    layers: int
    row: tuple
    window: int = None

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


@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    """A model's paged cache: its groups of layers (most models have one)
    and the storage type of every pool array."""
    groups: tuple
    dtype: str

    @classmethod
    def one_group(cls, names, layers, row, dtype):
        return cls((CacheGroup(tuple(names), layers, tuple(row)),), dtype)

    @property
    def names(self):
        """Every pool array's name, in the order the step takes them."""
        return tuple(n for g in self.groups for n in g.names)


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
        return CacheGeometry.one_group(
            ("kc", "vc"), config.num_layers, (nh, config.hidden_size // nh),
            str(jnp.dtype(config.compute_dtype or "float32")))

    def kernel_ok(self, config, mp, page_size):
        nh = config.num_heads
        # (a quantized pool's rows are narrower than the compute dtype's)
        return paged_kernel_supported(
            nh // mp, config.hidden_size // nh, page_size,
            why="serving engine", itemsize=jnp.dtype(
                config.compute_dtype or "float32").itemsize)

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
