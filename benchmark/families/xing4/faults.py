"""Planted faults of the xing4 family, for the readings that the cell's limit
is set from (``tools/serve_readings.py``): each is a context in which the
family's plain reference computes a model that is wrong in one way, so that
put in the program's place it has to come out not correct. Not part of the
family's interface and never used by a run of the benchmark."""
import contextlib

import jax.numpy as jnp

from . import reference as ref
from . import weights as W


@contextlib.contextmanager
def _patched(module, name, new):
    old = getattr(module, name)
    setattr(module, name, new)
    try:
        yield
    finally:
        setattr(module, name, old)


def _rotate_halves(x, cfg):
    """The wrong pairing: (i, i + rope/2) turn together, where the model
    pairs (2i, 2i+1)."""
    cos, sin = ref.rotary_cos_sin(x, cfg)
    h = x.shape[-1] // 2
    a, b = x[..., :h], x[..., h:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def wrong_rotary_pairing():
    return _patched(ref, "rotate", _rotate_halves)


def dropped_shared_expert():
    make = W.layer_leaves

    def without_shared(cfg, key, layer, moe, dtype, experts=True):
        p = make(cfg, key, layer, moe, dtype, experts)
        if moe:
            p["shared_down_w"] = jnp.zeros_like(p["shared_down_w"])
        return p

    return _patched(W, "layer_leaves", without_shared)


def unscaled_routed_weights():
    """The routed experts' weights without ``routed_scaling_factor``: half
    of what the routed experts add goes missing."""
    route = ref.route

    def unscaled(xn, p, cfg, mm):
        return route(xn, p, cfg, mm) / cfg["routed_scaling_factor"]

    return _patched(ref, "route", unscaled)


def capacity_dropped_tokens():
    """A static capacity of T k / E tokens an expert (capacity factor 1),
    filled in the order of the positions, the rest dropped: what a GShard
    layer does and a dropless one must not."""
    route = ref.route

    def capped(xn, p, cfg, mm):
        w = route(xn, p, cfg, mm)                                # [T, E]
        cap = -(-w.shape[0] * cfg["num_experts_per_tok"]
                // cfg["n_routed_experts"])
        place = jnp.cumsum(w > 0, axis=0)    # a token's place in the queue
        return jnp.where(place <= cap, w, 0.0)

    return _patched(ref, "route", capped)


FAULTS = {"wrong_rotary_pairing": wrong_rotary_pairing,
          "dropped_shared_expert": dropped_shared_expert,
          "unscaled_routed_weights": unscaled_routed_weights,
          "capacity_dropped_tokens": capacity_dropped_tokens}
