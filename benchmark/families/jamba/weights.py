"""The jamba family's weights, made on the device from ``--seed`` in the type
they are served in. The benchmark makes them and hands them to the program;
the plain reference makes the same values again from the seed, a layer at a
time, and so takes nothing that the program has touched.

``cfg`` is a configuration file's dict under the published key names
(``hidden_size``, ``attn_layer_offset``, ``mamba_d_state`` ...).

Layout, the tree the program's entry points take: ``wte [V, H]`` (the head
is tied to it), ``normf_g``, and each layer's leaves (its mixer, its MLP and
both norms) stacked by the kind of its mixer: ``mamba`` (``in_w [H, 2 Di]``
whose halves are x and z, ``conv_w [d_conv, Di]`` oldest tap first,
``conv_b``, ``x_w [Di, dt_rank + 2 N]`` whose parts are dt, B, C,
``dt_norm_g``, ``b_norm_g``, ``c_norm_g``, ``dt_w [dt_rank, Di]``, ``dt_b``,
``A_log [N, Di]``, ``D``, ``out_w``) and ``attn`` (``wq, wk, wv, wo``), both
with ``mixer_norm_g``, ``ffn_norm_g``, ``gate_w``, ``up_w``, ``down_w``,
each stack in the layers' order.

Values (the configuration's ``assumed`` lists them): every matrix N(0,
``initializer_range``); norm gains 1; Mamba's own initialisation where it
has one (arXiv:2312.00752 and its code): ``A_log[n, :] = log(n + 1)``, ``D =
1``, ``dt_b`` the inverse softplus of a dt log-uniform in [1e-3, 1e-1]; the
taps and the convolution's bias uniform in +-1/sqrt(d_conv), a Conv1d's
default. Every value is a function of (seed, leaf, absolute layer), so any
layer can be made again alone."""
import math

import jax
import jax.numpy as jnp

from benchmark.harness.weights import seed_key

TOP = ("wte", "normf_g")
# every leaf's place in the seed's keys: append, never reorder
NAMES = TOP + (
    "mixer_norm_g", "ffn_norm_g", "gate_w", "up_w", "down_w", "in_w",
    "conv_w", "conv_b", "x_w", "dt_norm_g", "b_norm_g", "c_norm_g", "dt_w",
    "dt_b", "A_log", "D", "out_w", "wq", "wk", "wv", "wo")


def head_dim(cfg):
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def d_inner(cfg):
    return cfg["mamba_expand"] * cfg["hidden_size"]


def is_mamba(cfg, layer):
    return layer % cfg["attn_layer_period"] != cfg["attn_layer_offset"]


def layer_shapes(cfg, mamba):
    """{leaf: shape} of one layer of the kind."""
    H, F = cfg["hidden_size"], cfg["intermediate_size"]
    sh = {"mixer_norm_g": (H,), "ffn_norm_g": (H,), "gate_w": (H, F),
          "up_w": (H, F), "down_w": (F, H)}
    if mamba:
        Di, N, R = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
        sh.update({"in_w": (H, 2 * Di), "conv_w": (cfg["mamba_d_conv"], Di),
                   "conv_b": (Di,), "x_w": (Di, R + 2 * N),
                   "dt_norm_g": (R,), "b_norm_g": (N,), "c_norm_g": (N,),
                   "dt_w": (R, Di), "dt_b": (Di,), "A_log": (N, Di),
                   "D": (Di,), "out_w": (Di, H)})
    else:
        d, nq = head_dim(cfg), cfg["num_attention_heads"]
        nkv = cfg["num_key_value_heads"]
        sh.update({"wq": (H, nq * d), "wk": (H, nkv * d),
                   "wv": (H, nkv * d), "wo": (nq * d, H)})
    return sh


def _value(cfg, name, key, shape, dtype):
    f32 = jnp.float32
    if name.endswith("_g") or name == "D":
        a = jnp.ones(shape, f32)
    elif name == "A_log":
        a = jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[0] + 1, dtype=f32))[:, None], shape)
    elif name == "dt_b":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        a = dt + jnp.log(-jnp.expm1(-dt))          # softplus(a) = dt
    elif name.startswith("conv_"):
        lim = 1.0 / math.sqrt(cfg["mamba_d_conv"])
        a = jax.random.uniform(key, shape, f32, -lim, lim)
    else:
        a = cfg["initializer_range"] * jax.random.normal(key, shape, f32)
    return a.astype(dtype)


def _leaf_key(key, name, layer=None):
    k = jax.random.fold_in(key, NAMES.index(name))
    return k if layer is None else jax.random.fold_in(k, layer)


def top_leaf(cfg, key, name, dtype):
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    shape = {"wte": (V, H), "normf_g": (H,)}[name]
    return _value(cfg, name, _leaf_key(key, name), shape, dtype)


def layer_leaves(cfg, key, layer, mamba, dtype):
    """One layer's leaves; ``layer`` is the absolute layer (may be traced),
    ``mamba`` its kind."""
    return {name: _value(cfg, name, _leaf_key(key, name, layer), shape,
                         dtype)
            for name, shape in layer_shapes(cfg, mamba).items()}


def make_tree(cfg, key, dtype):
    """The whole tree (traceable: call it under one jit)."""
    tree = {n: top_leaf(cfg, key, n, dtype) for n in TOP}
    layers = range(cfg["num_hidden_layers"])
    for stack, mamba in (("mamba", True), ("attn", False)):
        at = jnp.asarray([l for l in layers if is_mamba(cfg, l) == mamba],
                         jnp.int32)
        tree[stack] = jax.vmap(
            lambda l, mamba=mamba: layer_leaves(cfg, key, l, mamba, dtype))(at)
    return tree


def make_weights(cfg, seed, dtype, out_shardings=None):
    """One jitted call: the tree on the device, in ``dtype``."""
    fn = jax.jit(lambda k: make_tree(cfg, k, jnp.dtype(dtype)),
                 out_shardings=out_shardings)
    return fn(seed_key(seed))
