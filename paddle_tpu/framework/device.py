"""Device/place API (ref: python/paddle/device/__init__.py).

On TPU there is one accelerator type; jax manages placement. We keep Place
objects for API parity and route `set_device` to jax default-device selection.
"""
from __future__ import annotations

import jax


class Place:
    def __init__(self, kind: str, device_id: int = 0):
        self._kind = kind
        self._id = device_id

    def __repr__(self):
        return f"Place({self._kind}:{self._id})" if self._kind != "cpu" else "Place(cpu)"

    def __eq__(self, other):
        return isinstance(other, Place) and (self._kind, self._id) == (other._kind, other._id)


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu")


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class CUDAPlace(TPUPlace):
    """Alias for scripts written against the reference's GPU API: maps to the
    local accelerator (ref CUDAPlace semantics -> accelerator device n)."""


_current = None


def _default_kind() -> str:
    return jax.default_backend()  # "tpu" | "cpu" | ...


def set_device(device: str):
    """paddle.device.set_device("tpu:0"|"cpu"|"gpu:0") parity; gpu maps to tpu."""
    global _current
    kind, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    kind = {"gpu": "tpu", "cuda": "tpu", "tpu": "tpu", "cpu": "cpu"}.get(kind, kind)
    dev = jax.devices(kind)[idx]  # raises when the machine has no such device
    jax.config.update("jax_default_device", dev)
    _current = f"{kind}:{idx}" if kind != "cpu" else "cpu"
    return _current


def get_device() -> str:
    if _current is not None:
        return _current
    kind = _default_kind()
    return "cpu" if kind == "cpu" else f"{kind}:0"


def get_all_devices():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def device_count() -> int:
    return jax.device_count()


def is_compiled_with_cuda() -> bool:  # API parity; we are a TPU framework
    return False


def is_compiled_with_tpu() -> bool:
    return True


class XPUPlace(Place):
    def __init__(self, *a):
        raise NotImplementedError("XPU is out of scope on the TPU build")


class IPUPlace(Place):
    def __init__(self, *a):
        raise NotImplementedError("IPU is out of scope on the TPU build")


def get_cudnn_version():
    return None  # no cuDNN in an XLA/TPU stack


def is_compiled_with_xpu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    return False  # XLA plays CINN's role


def is_compiled_with_rocm():
    return False


def is_compiled_with_custom_device(device_type=None):
    return False


def get_all_device_type():
    return ["cpu", "tpu"]


def get_all_custom_device_type():
    return []


def get_available_device():
    import jax
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return []


class Stream:
    """XLA orders work internally; streams surface as no-op handles
    (ref: device/cuda/streams.py)."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


_current_stream = Stream()


def current_stream(device=None):
    return _current_stream


def set_stream(stream):
    global _current_stream
    _current_stream = stream
    return stream


import contextlib as _ctx


@_ctx.contextmanager
def stream_guard(stream):
    old = current_stream()
    set_stream(stream)
    try:
        yield
    finally:
        set_stream(old)


def synchronize(device=None):
    """Block until all queued device work completes."""
    import jax
    (jax.device_put(0) + 0).block_until_ready()
