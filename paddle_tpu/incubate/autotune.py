"""Auto-tuning config (ref: python/paddle/incubate/autotune.py set_config).

The reference toggles exhaustive cuDNN kernel search, NCHW/NHWC layout
rewriting, and DataLoader num_workers search. The TPU/XLA analogs:

- kernel: XLA's own autotuner picks MXU tilings during compilation; what a
  user controls is the persistent compilation cache that makes those
  choices pay off across processes. kernel.enable wires it.
- layout: XLA performs layout assignment in-graph (there is no user-visible
  NCHW/NHWC rewrite to make); the setting is recorded and surfaced via
  get_config() so callers can branch on it.
- dataloader: enable lets paddle_tpu.io.DataLoader pick a prefetch worker
  count instead of the user-provided one.
"""
from __future__ import annotations

import json
import warnings

_CONFIG = {
    "kernel": {"enable": False, "tuning_range": [1, 10]},
    "layout": {"enable": False},
    "dataloader": {"enable": False},
}


def set_config(config=None):
    """Accepts a dict, a json-file path, or None (enable everything) —
    ref incubate/autotune.py:24."""
    if config is None:
        for section in _CONFIG.values():
            section["enable"] = True
        _apply()
        return
    if isinstance(config, str):
        try:
            with open(config) as f:
                config = json.load(f)
        except Exception as e:  # noqa: BLE001 — parity: warn, keep defaults
            warnings.warn(f"Load config error: {e}; "
                          "use default configuration for auto-tuning.")
            config = {}
    for key, val in (config or {}).items():
        if key not in _CONFIG:
            warnings.warn(f"Unknown autotune section {key!r}")
            continue
        if not isinstance(val, dict):
            warnings.warn(f"autotune section {key!r} must be a dict")
            continue
        _CONFIG[key].update(val)
    _apply()


def get_config():
    return {k: dict(v) for k, v in _CONFIG.items()}


def _apply():
    if _CONFIG["kernel"]["enable"]:
        from ..framework.compilation_cache import ensure_persistent_cache
        ensure_persistent_cache()


def dataloader_num_workers(requested):
    """Called by io.DataLoader: returns the tuned worker count when
    dataloader autotune is on, else the requested one."""
    if not _CONFIG["dataloader"]["enable"]:
        return requested
    import os
    return max(requested, min(4, max(1, (os.cpu_count() or 2) // 2)))
