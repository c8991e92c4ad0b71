"""The comparison that decides ``correct``: per-leaf norms of trees, the
worst-leaf gap between the program's norms and the reference's, and the
served tokens' logit gap. Each number compared is printed beside its limit.

A tree is a dict of leaves and of groups (dicts of leaves stacked over the
layers). What is particular to a model comes from its family's ``weights``
module (``W`` below): ``leaf_parts(name)``, the parts of a leaf's last axis
whose norms are compared apart, and ``seed_leaves(cfg, key, dtype)``, every
leaf made again from the seed."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .weights import seed_key

F32 = jnp.float32


def _parts_sq(a, n):
    """Sums of squares of the n equal parts of the last axis."""
    lead = a.shape[:-1]
    an = a.reshape(lead + (n, a.shape[-1] // n))
    return jnp.sum(jnp.square(an), axis=tuple(i for i in range(an.ndim)
                                              if i != an.ndim - 2))


def _sq(name, a, leaf_parts):
    a = a.astype(F32)
    parts = leaf_parts(name)
    if parts:
        t = _parts_sq(a, len(parts))
        return {f"{name}.{p}": t[i] for i, p in enumerate(parts)}
    return {name: jnp.sum(jnp.square(a))}


@functools.partial(jax.jit, static_argnames=("leaf_parts",))
def leaf_sq_norms(tree, leaf_parts):
    """{leaf name: sum of squares}; a group's leaves take all layers
    together."""
    out = {}
    for name, a in tree.items():
        if isinstance(a, dict):
            for bn, ba in a.items():
                out.update({f"{name}.{k}": v
                            for k, v in _sq(bn, ba, leaf_parts).items()})
        else:
            out.update(_sq(name, a, leaf_parts))
    return out


@functools.lru_cache(maxsize=None)
def _change_fn(W, cfg_items, dtype):
    cfg = dict(cfg_items)
    dtype = jnp.dtype(dtype)

    @jax.jit
    def go(tree, key):
        out = {}
        for name, layers, make in W.seed_leaves(cfg, key, dtype):
            if layers is None:
                d = tree[name].astype(F32) - make().astype(F32)
                out.update(_sq(name, d, W.leaf_parts))
                continue

            def layer(l, name=name, make=make):
                res = {}
                for bn, init in make(l).items():
                    d = tree[name][bn][l].astype(F32) - init.astype(F32)
                    res.update(_sq(bn, d, W.leaf_parts))
                return res

            per_layer = jax.lax.map(layer, jnp.arange(layers))
            out.update({f"{name}.{k}": jnp.sum(v)
                        for k, v in per_layer.items()})
        return out

    return go


def change_sq_norms(tree, W, cfg, seed, dtype):
    """{leaf name: sum of squares of (leaf now - leaf as the seed made it)},
    the seed's leaves made again one layer at a time."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float))))
    return _change_fn(W, items, str(dtype))(tree, seed_key(seed))


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
