"""Distributed environment: device mesh management.

Re-design of the reference's process-group world (ref: python/paddle/
distributed/parallel.py, collective.py). The TPU-native model is
single-controller SPMD: one Python process drives all chips through a
`jax.sharding.Mesh`; "ranks" are mesh coordinates, "process groups" are named
mesh axes, and NCCL communicators are replaced by XLA collectives over ICI.

Multi-host TPU pods: call `init_parallel_env()` which routes to
`jax.distributed.initialize()` when TPU pod env vars are present; jax then
presents every chip in the pod in `jax.devices()` and the same single-
controller code scales out (DCN handled by the runtime).
"""
from __future__ import annotations

import os

import numpy as np
import jax
from jax.sharding import Mesh, PartitionSpec, NamedSharding

_global_mesh: Mesh | None = None
_initialized = False

# canonical hybrid-parallel axis order, outermost first. mp innermost so
# tensor-parallel collectives ride neighboring ICI links; ep next-innermost
# so the MoE all_to_all stays on near links too
HYBRID_AXES = ("pp", "dp", "sharding", "sp", "ep", "mp")


def init_parallel_env():
    """ref: paddle.distributed.init_parallel_env."""
    global _initialized
    if _initialized:
        return
    # several worker hosts (a one-host machine lists just itself and has
    # nothing to initialise) or a multi-slice coordinator
    if ("," in os.environ.get("TPU_WORKER_HOSTNAMES", "")
            or "MEGASCALE_COORDINATOR_ADDRESS" in os.environ):
        jax.distributed.initialize()
    _initialized = True


def world_size():
    return jax.device_count()


get_world_size = world_size


def get_rank(group=None):
    return jax.process_index()


def device_count():
    return jax.local_device_count()


def is_initialized():
    return _initialized


def set_mesh(mesh: Mesh):
    global _global_mesh
    _global_mesh = mesh


def get_mesh() -> Mesh | None:
    return _global_mesh


def create_hybrid_mesh(dp=1, mp=1, pp=1, sharding=1, sp=1, ep=1,
                       devices=None):
    """Build the hybrid-parallel mesh. Degrees must multiply to device count
    (a trailing dp fill-in is applied when dp == -1)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    known = mp * pp * sharding * sp * ep
    if dp == -1:
        assert n % known == 0, f"{n} devices not divisible by {known}"
        dp = n // known
    total = dp * known
    assert total <= n, (f"hybrid degrees dp{dp}×sharding{sharding}×pp{pp}×sp{sp}"
                        f"×mp{mp}×ep{ep}={total} > {n} devices")
    devices = list(devices)[:total]  # sub-mesh when degrees underfill the slice
    shape = dict(zip(HYBRID_AXES, (pp, dp, sharding, sp, ep, mp)))
    arr = np.array(devices).reshape(tuple(shape[a] for a in HYBRID_AXES))
    mesh = Mesh(arr, HYBRID_AXES)
    set_mesh(mesh)
    return mesh


def create_single_axis_mesh(axis, n=None, devices=None):
    """Mesh with exactly ONE named axis (e.g. ('mp',) or ('dp',)) — the
    layout interpret-mode fused GEMM+collective kernels require (jax<0.5's
    remote-DMA discharge rule supports a single named axis; see
    comm_backend.fused_mesh_ok). On a real TPU create_hybrid_mesh works
    for the fused backend too."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices) if n is None else int(n)
    assert n <= len(devices), (f"create_single_axis_mesh({axis!r}, {n}) "
                               f"needs {n} devices, only "
                               f"{len(devices)} available")
    mesh = Mesh(np.array(devices[:n]), (axis,))
    set_mesh(mesh)
    return mesh


def replicated_sharding(mesh=None):
    mesh = mesh or _global_mesh
    return NamedSharding(mesh, PartitionSpec())


class ParallelEnv:
    """ref: paddle.distributed.ParallelEnv (legacy accessor)."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return world_size()

    @property
    def device_id(self):
        return 0

    @property
    def dev_id(self):
        return 0
