"""The per-slot operands of a paged dispatch as ONE ``int32`` vector, and
its small outputs as one: the layout both sides of the dispatch read.

The host holds every per-slot quantity of the fused step (engine.py) and
sends it with each dispatch. Sent one array at a time that is nine to
eleven ``device_put``s for 64 bytes to 8 KB each, and three fetches back;
here they travel as one buffer and come back as one. ``StepLayout`` fixes,
for a dispatch shape ``(B, T)``, the cache groups' table widths and
whether adapter ids ride, ``name -> Field(offset, shape, dtype)`` over the
vector. The host fills a buffer through ``views`` (numpy views of the
buffer, one a field, at the field's own dtype); the jitted step recovers
the fields with ``unpack``: static slices, a bitcast for ``float32`` and
``uint32`` (their bit patterns ride), ``!= 0`` for the bools. Every field
comes back bit for bit, so the step computes what it computed from
separate operands.

The step's small outputs go the other way through ``pack_out`` (traced)
and ``split_out`` (host): the next tokens ``[B]``, the new key data
``[B, 2]``, the per-slot verdict ``[B]`` where the anomaly guard is on,
and the model's statistics (``int32``) where it returns any.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property
from typing import NamedTuple

import numpy as np
import jax.numpy as jnp
from jax import lax


class Field(NamedTuple):
    offset: int
    shape: tuple
    dtype: np.dtype     # what the step sees; a bool rides as int32 0 / 1

    @property
    def size(self):
        return int(np.prod(self.shape))


class Operands(NamedTuple):
    """What ``unpack`` hands the step. ``table`` is the one cache group's
    page table, or a tuple of one a group (a state group's is the slots'
    numbers); ``adapter_ids`` is None where no adapter rides."""
    ids: object
    start: object
    valid: object
    emit: object
    table: object
    do_sample: object
    temperature: object
    top_p: object
    key_data: object
    adapter_ids: object = None


@dataclass(frozen=True)
class StepLayout:
    """The layout of one dispatch shape. Hashable on what fixes it, so it
    keys the jitted step as a static argument: one trace a layout, as the
    separate operands gave one trace a shape."""
    B: int
    T: int
    table_widths: tuple         # one a cache group; 0: a state group ([B])
    adapters: bool = False

    @cached_property
    def fields(self):
        B = self.B
        spec = [("ids", (B, self.T), np.int32), ("start", (B,), np.int32),
                ("valid", (B,), np.int32), ("emit", (B,), np.bool_)]
        spec += [(f"table{g}", (B, w) if w else (B,), np.int32)
                 for g, w in enumerate(self.table_widths)]
        spec += [("do_sample", (B,), np.bool_),
                 ("temperature", (B,), np.float32),
                 ("top_p", (B,), np.float32),
                 ("key_data", (B, 2), np.uint32)]
        if self.adapters:
            spec.append(("adapter_ids", (B,), np.int32))
        out, offset = {}, 0
        for name, shape, dtype in spec:
            out[name] = Field(offset, shape, np.dtype(dtype))
            offset += out[name].size
        return out

    @cached_property
    def size(self):
        return sum(f.size for f in self.fields.values())

    @property
    def exe(self):
        """The name the step is jitted under for this dispatch shape: an
        executable run reads ``jit_pt_paged_b16_t1(...)`` on a device
        trace, and the ``pt.serve.*`` spans of its dispatch carry it."""
        return f"pt_paged_b{self.B}_t{self.T}"

    @property
    def tables(self):
        """The names of the table fields, group after group."""
        return tuple(f"table{g}" for g in range(len(self.table_widths)))

    def views(self, buf):
        """``name -> a numpy view`` of ``buf`` (int32 ``[size]``) at the
        field's shape and dtype: assigning through a view IS the pack. A
        bool field's view is int32 (an assigned bool lands as 0 or 1)."""
        assert buf.dtype == np.int32 and buf.shape == (self.size,)
        out = {}
        for name, f in self.fields.items():
            flat = buf[f.offset:f.offset + f.size]
            if f.dtype != np.bool_:
                flat = flat.view(f.dtype)
            out[name] = flat.reshape(f.shape)
        return out

    def pack(self, views, **values):
        """Fill a buffer through its ``views``; every field is named."""
        assert values.keys() == self.fields.keys(), sorted(values)
        for name, value in values.items():
            views[name][...] = value

    def unpack(self, packed):
        """Traced: the fields of ``packed`` as ``Operands``, bit for bit."""
        got = {}
        for name, f in self.fields.items():
            x = lax.slice(packed, (f.offset,),
                          (f.offset + f.size,)).reshape(f.shape)
            if f.dtype == np.bool_:
                x = x != 0
            elif f.dtype != np.int32:
                x = lax.bitcast_convert_type(x, f.dtype)
            got[name] = x
        tables = tuple(got.pop(name) for name in self.tables)
        return Operands(table=tables[0] if len(tables) == 1 else tables,
                        **got)


def pack_out(nxt, key_data, ok=None, stats=None):
    """Traced: the step's small outputs as one int32 vector."""
    parts = [nxt.astype(jnp.int32),
             lax.bitcast_convert_type(key_data, jnp.int32).reshape(-1)]
    if ok is not None:
        parts.append(ok.astype(jnp.int32))
    if stats is not None:
        if stats.dtype != jnp.int32:
            raise TypeError(f"a model's statistics ride the step's output "
                            f"vector as int32, not {stats.dtype}")
        parts.append(stats.reshape(-1))
    return jnp.concatenate(parts)


def split_out(out, B, anomaly):
    """Host: ``pack_out``'s vector (a numpy array) as (next tokens [B],
    key data [B, 2] uint32, the verdict [B] bool or None, the statistics
    or None)."""
    nxt, keys, rest = out[:B], out[B:3 * B], out[3 * B:]
    ok = None
    if anomaly:
        ok, rest = rest[:B] != 0, rest[B:]
    return (nxt, keys.view(np.uint32).reshape(B, 2), ok,
            rest if rest.size else None)
