"""The GPT family's weights, made on the device from ``--seed`` in the type
they are served or trained in. The benchmark makes them and hands them to the
program; the plain reference makes the same values again from the seed, layer
by layer, and so takes nothing that the program has touched.

Layout: the functional GPT tree the program's entry points take
(``wte, wpe, lnf_g, lnf_b, head_w, blocks{...}`` with block leaves stacked
``[L, ...]``). Matrices are N(0, initializer_range), gains 1, biases 0."""
import jax
import jax.numpy as jnp

from benchmark.harness.weights import seed_key

TOP_MATRICES = ("wte", "wpe", "head_w")
BLOCK_MATRICES = ("qkv_w", "out_w", "up_w", "down_w")


def shapes(cfg):
    H, V, P = cfg["hidden_size"], cfg["vocab_size"], cfg["max_seq_len"]
    inner = cfg["ffn_mult"] * H
    top = {"wte": (V, H), "wpe": (P, H), "lnf_g": (H,), "lnf_b": (H,),
           "head_w": (H, V)}
    block = {"ln1_g": (H,), "ln1_b": (H,), "qkv_w": (H, 3 * H),
             "qkv_b": (3 * H,), "out_w": (H, H), "out_b": (H,),
             "ln2_g": (H,), "ln2_b": (H,), "up_w": (H, inner),
             "up_b": (inner,), "down_w": (inner, H), "down_b": (H,)}
    return top, block


def _leaf_key(key, name):
    names = TOP_MATRICES + BLOCK_MATRICES
    return jax.random.fold_in(key, names.index(name))


def _matrix(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _const(name, shape, dtype):
    return (jnp.ones if name.endswith("_g") else jnp.zeros)(shape, dtype)


def top_leaf(cfg, key, name, dtype):
    top, _ = shapes(cfg)
    if name in TOP_MATRICES:
        return _matrix(_leaf_key(key, name), top[name],
                       cfg["initializer_range"], dtype)
    return _const(name, top[name], dtype)


def layer_leaves(cfg, key, layer, dtype):
    """One layer's block weights; ``layer`` may be traced."""
    _, block = shapes(cfg)
    out = {}
    for name, shape in block.items():
        if name in BLOCK_MATRICES:
            k = jax.random.fold_in(_leaf_key(key, name), layer)
            out[name] = _matrix(k, shape, cfg["initializer_range"], dtype)
        else:
            out[name] = _const(name, shape, dtype)
    return out


def make_tree(cfg, key, dtype):
    """The whole tree (traceable: call it under one jit)."""
    top, _ = shapes(cfg)
    tree = {n: top_leaf(cfg, key, n, dtype) for n in top}
    tree["blocks"] = jax.vmap(lambda l: layer_leaves(cfg, key, l, dtype))(
        jnp.arange(cfg["num_layers"]))
    return tree


def make_weights(cfg, seed, dtype, out_shardings=None):
    """One jitted call: the tree on the device(s), in ``dtype``."""
    fn = jax.jit(lambda k: make_tree(cfg, k, jnp.dtype(dtype)),
                 out_shardings=out_shardings)
    return fn(seed_key(seed))


def seed_leaves(cfg, key, dtype):
    """Every leaf of the tree as the seed makes it, one layer at a time, for
    the comparison of the parameters' change: ``(name, None, make)`` with
    ``make()`` a top-level leaf, and ``(name, layers, make)`` with
    ``make(l)`` the dict of layer ``l``'s leaves (``l`` may be traced) of the
    group ``name`` that the tree holds stacked ``[layers, ...]``."""
    top, _ = shapes(cfg)
    for name in top:
        yield name, None, lambda name=name: top_leaf(cfg, key, name, dtype)
    yield "blocks", cfg["num_layers"], \
        lambda l: layer_leaves(cfg, key, l, dtype)


def leaf_parts(name):
    """The fused projection holds q, k and v side by side along its last
    axis; their gradients differ by orders of magnitude (k's bias has none
    under softmax), so each third's norm is compared on its own."""
    return ("q", "k", "v") if name.startswith("qkv_") else None
