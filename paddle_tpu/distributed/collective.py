"""Collective communication API (ref: python/paddle/distributed/communication/*).

Two execution contexts:
  * inside an SPMD region (shard_map / pjit-manual): lowers to XLA collectives
    (`psum`, `all_gather`, `ppermute`, `all_to_all`) over the named mesh axis —
    the ICI path, this is where training-time communication happens;
  * eager, single controller: tensors are global (the SPMD model has no
    per-rank eager view), so SUM-like collectives are identity when
    world_size==1 and otherwise interpreted as "already reduced" — matching
    how the reference's API behaves after gradient sync.

Groups are named mesh axes (default: all axes of the active mesh).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..tensor_impl import Tensor, as_tensor_data
from ..dispatch import apply as _apply
from . import env


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communication group = a named mesh axis (or tuple of axes)."""

    def __init__(self, axis_name, ranks=None):
        self.axis_name = axis_name
        self.ranks = ranks
        self.nranks = len(ranks) if ranks else None

    @property
    def name(self):
        return str(self.axis_name)

    def __repr__(self):
        return f"Group(axis={self.axis_name})"


_default_group = Group("dp")


def new_group(ranks=None, backend=None, axis_name=None):
    return Group(axis_name or "dp", ranks)


def get_group(gid=0):
    return _default_group


def _axis(group):
    if group is None:
        return _default_group.axis_name
    if isinstance(group, Group):
        return group.axis_name
    return group  # allow raw axis name strings


def _in_spmd(axis_name):
    """True when called under shard_map with this axis bound."""
    try:
        return axis_name in jax.core.get_axis_env().axis_sizes  # jax>=0.8 internal
    except Exception:
        try:
            lax.axis_index(axis_name)
            return True
        except Exception:
            return False


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    axis = _axis(group)
    if _in_spmd(axis):
        fn = {ReduceOp.SUM: lax.psum, ReduceOp.MAX: lax.pmax, ReduceOp.MIN: lax.pmin,
              ReduceOp.AVG: lax.pmean}.get(op)
        if op == ReduceOp.PROD:
            def fn(x, a):
                # sign-and-magnitude lowering: exp(psum(log|x|)) for the
                # magnitude with zeros masked to 1, sign from the parity of
                # the negative count, exact 0 when any member holds a 0 —
                # the naive exp(psum(log(x))) NaNs on zero/negative inputs
                # float64 magnitude when x64 is enabled (silently float32
                # otherwise): int32+ products overflow fp32's 24-bit mantissa
                xf = x.astype(jnp.float64)
                zeros = lax.psum((xf == 0).astype(jnp.int32), a)
                negs = lax.psum((xf < 0).astype(jnp.int32), a)
                mag = jnp.exp(lax.psum(
                    jnp.log(jnp.where(xf == 0, 1.0, jnp.abs(xf))), a))
                sign = jnp.where(negs % 2 == 0, 1.0, -1.0)
                res = jnp.where(zeros > 0, 0.0, sign * mag)
                if jnp.issubdtype(x.dtype, jnp.integer):
                    # exp/log round-trip is inexact; truncation toward zero
                    # would turn prod([2, 3]) = 5.9999995 into 5
                    res = jnp.round(res)
                return res.astype(x.dtype)
        out = _apply(lambda x: fn(x, axis), tensor, op_name="all_reduce")
        if isinstance(tensor, Tensor):
            tensor._data = out._data
            tensor._node = out._node
            tensor._out_idx = out._out_idx
            return tensor
        return out
    return tensor  # global view: already reduced


def all_gather(tensor_list, tensor=None, group=None, sync_op=True, axis=0):
    """Both reference signatures: all_gather(list, t) and functional return."""
    if tensor is None:
        tensor, tensor_list = tensor_list, None
    ax = _axis(group)
    if _in_spmd(ax):
        out = _apply(lambda x: lax.all_gather(x, ax, tiled=True), tensor,
                     op_name="all_gather")
    else:
        out = tensor
    if tensor_list is not None:
        n = env.world_size()
        from ..tensor import manipulation as M
        chunks = M.split(out, n, axis=0) if n > 1 else [out]
        tensor_list.extend(chunks)
        return None
    return out


def all_gather_object(obj_list, obj, group=None):
    obj_list.append(obj)


def reduce_scatter(tensor, tensor_or_list=None, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    ax = _axis(group)
    src = tensor_or_list if tensor_or_list is not None else tensor
    if _in_spmd(ax):
        def f(x):
            return lax.psum_scatter(x, ax, tiled=True)
        out = _apply(f, src, op_name="reduce_scatter")
        if tensor_or_list is not None and isinstance(tensor, Tensor):
            tensor._data = out._data
            return tensor
        return out
    return src


def broadcast(tensor, src=0, group=None, sync_op=True):
    ax = _axis(group)
    if _in_spmd(ax):
        def f(x):
            # take src's value on every member of the axis
            full = lax.all_gather(x, ax)
            return full[src]
        out = _apply(f, tensor, op_name="broadcast")
        if isinstance(tensor, Tensor):
            tensor._data = out._data
            return tensor
        return out
    return tensor


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    ax = _axis(group)
    if _in_spmd(ax):
        idx = lax.axis_index(ax)
        if tensor_list is not None:
            from ..tensor import manipulation as M
            stacked = M.stack(tensor_list, axis=0)
            out = _apply(lambda s: s[idx], stacked, op_name="scatter")
        else:
            out = _apply(lambda x: lax.dynamic_index_in_dim(x, idx, keepdims=False),
                         tensor, op_name="scatter")
        if isinstance(tensor, Tensor):
            tensor._data = out._data
            return tensor
        return out
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    ax = _axis(group)
    from ..tensor import manipulation as M
    if isinstance(in_tensor_list, (list, tuple)):
        x = M.stack(list(in_tensor_list), axis=0)
    else:
        x = in_tensor_list
    if _in_spmd(ax):
        out = _apply(lambda a: lax.all_to_all(a, ax, split_axis=0, concat_axis=0,
                                              tiled=False), x, op_name="alltoall")
    else:
        out = x
    if out_tensor_list is not None:
        out_tensor_list.extend(list(out))
        return None
    return out


def alltoall_single(in_tensor, out_tensor=None, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    ax = _axis(group)
    if _in_spmd(ax):
        out = _apply(lambda a: lax.all_to_all(a, ax, split_axis=0, concat_axis=0,
                                              tiled=True), in_tensor,
                     op_name="alltoall")
    else:
        out = in_tensor
    if out_tensor is not None and isinstance(out_tensor, Tensor):
        out_tensor._data = as_tensor_data(out)
        return None
    return out


def send(tensor, dst=0, group=None, sync_op=True):
    """Point-to-point on a ring: implemented as ppermute inside SPMD regions."""
    ax = _axis(group)
    if _in_spmd(ax):
        n = lax.axis_size(ax)
        perm = [(i, dst) for i in range(n)]
        return _apply(lambda x: lax.ppermute(x, ax, perm), tensor, op_name="send")
    return tensor


def recv(tensor, src=0, group=None, sync_op=True):
    ax = _axis(group)
    if _in_spmd(ax):
        n = lax.axis_size(ax)
        perm = [(src, i) for i in range(n)]
        out = _apply(lambda x: lax.ppermute(x, ax, perm), tensor, op_name="recv")
        if isinstance(tensor, Tensor):
            tensor._data = out._data
            return tensor
        return out
    return tensor


def p2p_shift(tensor, group=None, shift=1):
    """Ring shift (the TPU-native send/recv pair): every member passes its value
    `shift` steps around the axis. Used by pipeline & ring attention."""
    ax = _axis(group)
    def f(x):
        n = lax.axis_size(ax)
        perm = [(i, (i + shift) % n) for i in range(n)]
        return lax.ppermute(x, ax, perm)
    return _apply(f, tensor, op_name="p2p_shift")


def barrier(group=None):
    jax.block_until_ready(jnp.zeros(()))


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor):
        jax.block_until_ready(tensor._data)
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    # SPMD model has no single-destination reduce; psum everywhere is the
    # TPU-native equivalent (the extra copies are free vs. ICI latency)
    return all_reduce(tensor, op, group, sync_op)


def stream_allreduce(*a, **k):
    return all_reduce(*a, **k)


def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True):
    """Gather to dst (ref communication/gather.py). SPMD model: all_gather
    everywhere (a single-destination gather saves nothing on ICI); eager
    single-controller: every rank holds the same replicated value, so the
    gather list is world_size copies of it."""
    ax = _axis(group)
    if _in_spmd(ax):
        out = _apply(lambda x: lax.all_gather(x, ax), tensor,
                     op_name="gather")
        chunks = [out[i] for i in range(out.shape[0])]
    else:
        # independent copies: aliasing one Tensor world_size times would
        # make any in-place edit of one entry mutate all of them
        chunks = [Tensor(tensor._data) if isinstance(tensor, Tensor)
                  else tensor for _ in range(env.world_size())]
    if gather_list is not None:
        gather_list.clear()
        gather_list.extend(chunks)
    return chunks


def isend(tensor, dst=0, group=None):
    """Async send returns a waitable task (ref communication/isend); under
    the compiled SPMD model dispatch is already async, so the task's wait
    is a device sync."""
    res = send(tensor, dst, group, sync_op=False)

    class _Task:
        def wait(self, *a, **k):
            return wait(res)
    return _Task()


def irecv(tensor, src=0, group=None):
    res = recv(tensor, src, group, sync_op=False)

    class _Task:
        def wait(self, *a, **k):
            return wait(res)
    return _Task()


def broadcast_object_list(object_list, src=0, group=None):
    """Python-object broadcast (ref communication/broadcast.py). The
    single-controller owns every rank's python state, so the list is
    already consistent; kept for API parity."""
    return object_list


def scatter_object_list(out_object_list, in_object_list=None, src=0,
                        group=None):
    out_object_list.clear()
    if in_object_list:
        out_object_list.append(in_object_list[get_rank_in(group)])
    return out_object_list


def get_rank_in(group=None):
    """Rank within `group` (falls back to global rank for the world)."""
    from .env import get_rank
    rank = get_rank()
    ranks = getattr(group, "ranks", None) if group is not None else None
    if ranks:
        return list(ranks).index(rank) if rank in ranks else 0
    return rank


def destroy_process_group(group=None):
    """Reset mesh/env state (ref communication/group.py destroy)."""
    from . import env as _env
    if group is None:
        _env.set_mesh(None)


def is_available():
    return True
