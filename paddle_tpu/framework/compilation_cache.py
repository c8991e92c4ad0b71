"""Persistent XLA compilation cache — the one owner of
``jax_compilation_cache_dir``.

jax can serialize compiled executables to disk and reload them in later
processes (the TPU analog of the reference's cached CUDA kernel binaries +
cudnn autotune cache). Where it lives:

* ``JAX_COMPILATION_CACHE_DIR`` set: jax read it into its config at import
  and nothing here touches it — the deployment places the cache.
* otherwise ``<checkout>/.jax_cache``, computed from this package's own
  location (as io/native.py finds its build dir). The directory is part of
  the cache key, so it must not move with the caller's working directory.

Lazy by design: importing paddle_tpu must not create directories or mutate
jax config; the first build point (dispatch-cache entry, optimizer step,
TrainStep, to_static, HybridTrainStep, serving.Engine) triggers it.
``FLAGS_persistent_compilation_cache=False`` before that point leaves the
config untouched.
"""
from __future__ import annotations

import os
import threading

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")

_lock = threading.Lock()
_initialized = False


def ensure_persistent_cache():
    """Idempotent: enable jax's on-disk compilation cache once per process."""
    global _initialized
    if _initialized:
        return
    with _lock:
        if _initialized:
            return
        from .. import flags as _flags
        if not _flags._FLAGS.get("FLAGS_persistent_compilation_cache", True):
            return  # latch NOT set: enabling the flag later still works
        _initialized = True
        if not jax.config.jax_compilation_cache_dir:
            jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)


def cache_dir():
    """The active persistent-cache directory, or None when disabled."""
    return jax.config.jax_compilation_cache_dir
