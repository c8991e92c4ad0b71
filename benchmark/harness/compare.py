"""The comparison that decides ``correct``: per-leaf norms of trees, the
worst-leaf gap between the program's norms and the reference's, and the
served tokens' logit gap. Each number compared is printed beside its limit."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

F32 = jnp.float32


def _thirds_sq(a):
    """Sums of squares of the q, k, v thirds of the last axis."""
    lead = a.shape[:-1]
    a3 = a.reshape(lead + (3, a.shape[-1] // 3))
    return jnp.sum(jnp.square(a3), axis=tuple(i for i in range(a3.ndim)
                                              if i != a3.ndim - 2))


def _sq(name, a):
    a = a.astype(F32)
    if name.startswith("qkv_"):
        t = _thirds_sq(a)
        return {f"{name}.{p}": t[i] for i, p in enumerate("qkv")}
    return {name: jnp.sum(jnp.square(a))}


@jax.jit
def leaf_sq_norms(tree):
    """{leaf name: sum of squares}; block leaves take all layers together."""
    out = {}
    for name, a in tree.items():
        if name == "blocks":
            for bn, ba in a.items():
                out.update({f"blocks.{k}": v for k, v in _sq(bn, ba).items()})
        else:
            out.update(_sq(name, a))
    return out


@functools.lru_cache(maxsize=None)
def _change_fn(cfg_items, dtype):
    cfg = dict(cfg_items)
    dtype = jnp.dtype(dtype)

    @jax.jit
    def go(tree, key):
        out = {}
        for name, a in tree.items():
            if name == "blocks":
                continue
            d = a.astype(F32) - W.top_leaf(cfg, key, name, dtype).astype(F32)
            out.update(_sq(name, d))

        def layer(l):
            init = W.layer_leaves(cfg, key, l, dtype)
            res = {}
            for bn in init:
                d = tree["blocks"][bn][l].astype(F32) - init[bn].astype(F32)
                res.update(_sq(bn, d))
            return res

        per_layer = jax.lax.map(layer, jnp.arange(cfg["num_layers"]))
        out.update({f"blocks.{k}": jnp.sum(v) for k, v in per_layer.items()})
        return out

    return go


def change_sq_norms(tree, cfg, seed, dtype):
    """{leaf name: sum of squares of (leaf now - leaf as the seed made it)},
    the seed's leaves made again one layer at a time."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float))))
    return _change_fn(items, str(dtype))(tree, W.seed_key(seed))


def norms(sq):
    return {k: float(np.sqrt(np.float64(v))) for k, v in
            jax.device_get(sq).items()}


def worst_leaf_gap(prog, ref, skip=()):
    """max over leaves of |prog - ref| / max(ref, median ref): the gap between
    the norms, against the reference's norm of that leaf or of the median
    leaf, whichever is larger. Returns (gap, leaf)."""
    names = [n for n in ref if n not in skip]
    med = float(np.median([ref[n] for n in names]))
    worst, at = 0.0, None
    for n in names:
        g = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        if g >= worst:
            worst, at = g, n
    return worst, at


def nought_gradient_leaves(ref_grad_norms):
    """Leaves whose gradient is nought to rounding in the reference (under a
    thousandth of the median leaf's): they move under Adam by round-off alone
    and are left out of the parameters' change."""
    med = float(np.median(list(ref_grad_norms.values())))
    return {n for n, v in ref_grad_norms.items() if v < 1e-3 * med}


def logit_gaps(ref_logits, rows):
    """For each served token of each sampled row, how far its reference logit
    lies below the reference's best at that position. ``rows`` is a list of
    (prompt_len, tokens). Returns one array of gaps per row."""
    out = []
    for i, (plen, toks) in enumerate(rows):
        n = len(toks)
        lg = ref_logits[i, plen - 1:plen - 1 + n]
        best = jnp.max(lg, axis=-1)
        at = jnp.take_along_axis(lg, jnp.asarray(toks)[:, None], axis=-1)[:, 0]
        out.append(np.asarray(best - at, np.float64))
    return out


class Checks:
    """The numbers compared, each beside its limit."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit):
        value = float(value)
        ok = bool(np.isfinite(value) and value <= limit)
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": ok})
        return ok

    @property
    def correct(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def as_dict(self):
        return {r["name"]: {"value": r["value"], "limit": r["limit"]}
                for r in self.rows}

    def lines(self):
        return [f"compared {r['name']} = {r['value']:.6g} limit {r['limit']:.6g}"
                f" {'ok' if r['ok'] else 'NOT CORRECT'}" for r in self.rows]
