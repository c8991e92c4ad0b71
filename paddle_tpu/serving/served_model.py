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
  many. A request is admitted when every paged group can hold it. A
  STATE group (``paged=False``) is not paged at all: its layers keep
  ``[layers, slots, *row]`` whatever the context (the rows before a short
  convolution, a recurrent state), so it has no table, no allocator and
  nothing to reserve; the step finds a slot's state by the slot's number,
  which rides where a paged group's table does. The STEP owns its
  lifetime: it starts a slot's state from zero where the slot's ``start``
  is 0 and leaves it alone where ``valid`` is 0, so slot reuse,
  pre-emption with recompute and idle slots need nothing of the host. The
  first group is a paged one;
* its **paged forward**: ``forward(params, config, ids, pools, start, valid,
  table, page_size, ...) -> (logits [B, V], pools, stats)``, the fused
  chunk/decode step over those pools (the pools the layer scans' carry).
  ``pools`` is every group's arrays in the geometry's order, flat; ``table``
  is the one group's table ``[B, pages]`` or, with several groups, a tuple
  of them in the groups' order (a state group's entry is the slots'
  numbers ``[B]``). ``stats`` is None or one small array that
  leaves the device with the tokens and goes to ``record(stats, kind,
  config)`` on the host.

A configuration object names its model through a ``served_model`` attribute;
one without it is the GPT family, whose seam is here. Scheduler, admission,
page tables, chunk ladder, sampling and the phase clock are the engine's and
shared by every model; what a model does not support yet (``unsupported``:
spec, quant, adapters, mp, kv_transfer, prefix_cache) the engine refuses by
name at construction. Prefix sharing and copy-on-write work on the first
group alone, so a model with a window group lists ``prefix_cache``: a ring
page holds different positions over a request's life. So does a model with
a state group: a shared page does not bring the state at its end."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from ..models.generation import _cfg_key, _cfg_view, _logical_qkv, \
    _stored_qkv
from .paged_attention import paged_forward, paged_kernel_supported, \
    pool_head_dim


@dataclasses.dataclass(frozen=True)
class CacheGroup:
    """The layers of a model that share one page table: ``names`` one per
    pool array a layer keeps, ``row`` a token's row in each as the model
    writes it (GPT: ``(nh, d)`` twice; grouped heads: ``(kv heads, d)``; a
    latent cache: ``(576,)`` once), ``window`` None or the positions a
    layer of the group attends to (its slot is then a ring). ``paged``
    False is a state group: ``row`` is then what a SLOT keeps a layer
    whatever its context (``(rows, hidden)``), the arrays are ``[layers,
    slots, *row]`` and no table maps them. ``dtype`` None stores the group
    in the geometry's type; a group that must keep another (a recurrent
    state summed over thousands of steps, float32 beside bfloat16 pages)
    names it."""
    names: tuple
    layers: int
    row: tuple
    window: int = None
    paged: bool = True
    dtype: str = None

    def pool_shape(self, num_pages, page_size):
        """On the device a row's last axis is whole lanes (``pool_head_dim``:
        row-major is then the TPU's default layout, PERF.md PR 26)."""
        return (self.layers, num_pages, page_size) + self.row[:-1] + \
            (pool_head_dim(self.row[-1]),)

    def state_shape(self, num_slots):
        """A state group's arrays: a row a slot a layer, whole lanes too."""
        return (self.layers, num_slots) + self.row[:-1] + \
            (pool_head_dim(self.row[-1]),)

    def row_bytes(self, itemsize):
        """Bytes of one row in every array of the group at the model's own
        width: a token a layer (paged), a slot a layer (state)."""
        return len(self.names) * int(np.prod(self.row)) * itemsize

    def logical(self, pool):
        """A host copy of a pool array (or of pages of it) at the model's
        own row width, contiguous: what snapshots, page payloads and the
        chaos hooks see."""
        return np.ascontiguousarray(np.asarray(pool)[..., :self.row[-1]])


@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    """A model's paged cache: its groups of layers (most models have one)
    and the storage type of every pool array whose group names none."""
    groups: tuple
    dtype: str

    @classmethod
    def one_group(cls, names, layers, row, dtype):
        return cls((CacheGroup(tuple(names), layers, tuple(row)),), dtype)

    @property
    def names(self):
        """Every pool array's name, in the order the step takes them."""
        return tuple(n for g in self.groups for n in g.names)

    @property
    def paged(self):
        """The groups that a page table maps, in order."""
        return tuple(g for g in self.groups if g.paged)


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

    def observe(self, kind, valid):
        """A chunk or decode dispatch's ``valid`` [B] as the host sent it
        (its own copy, no sync): where a model counts the work of a layer
        of its own."""


class _GPTServed(ServedModel):
    name = "gpt"

    def key(self, config):
        return _cfg_key(config)

    def view(self, key):
        return _cfg_view(key)

    def prepare(self, params, config):
        # undo head-major qkv storage (sequence-parallel HybridTrainStep)
        # once at construction: single-chip decode splits qkv logically;
        # then keep a full-precision qkv stack as the step reads it
        return _stored_qkv(_logical_qkv(params, config))

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
