"""The lfm2 family's weights, made on the device from ``--seed`` in the type
they are served in. The benchmark makes them and hands them to the program;
the plain reference makes the same values again from the seed, a layer (and
an expert) at a time, and so takes nothing that the program has touched.

``cfg`` is a configuration file's dict under the published key names
(``hidden_size``, ``num_key_value_heads``, ``num_experts``, ``layer_types``,
``conv_L_cache`` ...).

Layout, the tree the program's entry points take: ``wte [V, H]`` (the head
is tied to it), ``normf_g`` (the published ``embedding_norm``), a layer's MLP
leaves and its two norms stacked by the kind of its MLP (``dense``: the
leading ``num_dense_layers``; ``moe``: the rest, expert leaves ``[layers,
experts, ...]``), and its operator's leaves stacked by the kind of operator
(``conv``: ``in_w [H, 3H]`` whose thirds are B, C, X in that order, ``taps
[H, L]``, ``out_w``; ``attn``: ``wq, wk, wv, wo, q_norm_g, k_norm_g``), each
stack in the layers' order.

Values (the configuration's ``assumed`` lists them): every matrix, the taps
among them, N(0, ``initializer_range``); norm gains 1; the router's selection
bias (``expert_bias``) N(0, 0.1). A routed expert's matrix is ``sqrt(1 -
EXPERT_OWN^2)`` of a matrix that its layer's experts share plus
``EXPERT_OWN`` of one that is its own (both N(0, 1), so the sum is too):
experts as sparse upcycling leaves them (Komatsuzaki et al. 2022,
arXiv:2212.05055). Why: where rounding flips a token's last chosen expert
against the next, two independent seeded experts swap one arbitrary vector
for another, and one such flip weighs as much as the float8 control does
(PERF.md section 6, PR 28); a trained router's near-tie lies between experts
that serve the token about alike, as these do. Every value is a function of
(seed, leaf, absolute layer, expert), so any slice can be made again alone."""
import math

import jax
import jax.numpy as jnp

from benchmark.harness.weights import seed_key

TOP = ("wte", "normf_g")
EXPERT_LEAVES = ("experts_gate_w", "experts_up_w", "experts_down_w")
EXPERT_OWN = 0.1             # share of a routed expert's matrix that is its own
CONV = "conv"
# every leaf's place in the seed's keys: append, never reorder
NAMES = TOP + (
    "operator_norm_g", "ffn_norm_g", "in_w", "taps", "out_w", "wq", "wk",
    "wv", "wo", "q_norm_g", "k_norm_g", "gate_w", "up_w", "down_w",
    "router_w", "router_bias", "experts_gate_w", "experts_up_w",
    "experts_down_w")


def head_dim(cfg):
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def operator_shapes(cfg, conv):
    """{leaf: shape} of one layer's operator."""
    H, d = cfg["hidden_size"], head_dim(cfg)
    if conv:
        return {"in_w": (H, 3 * H), "taps": (H, cfg["conv_L_cache"]),
                "out_w": (H, H)}
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return {"wq": (H, nq), "wk": (H, nkv), "wv": (H, nkv), "wo": (nq, H),
            "q_norm_g": (d,), "k_norm_g": (d,)}


def mlp_shapes(cfg, moe):
    """{leaf: shape} of one layer's MLP and its two norms; an expert leaf's
    shape is one expert's."""
    H = cfg["hidden_size"]
    sh = {"operator_norm_g": (H,), "ffn_norm_g": (H,)}
    if moe:
        F = cfg["moe_intermediate_size"]
        sh.update({"router_w": (H, cfg["num_experts"]),
                   "router_bias": (cfg["num_experts"],),
                   "experts_gate_w": (H, F), "experts_up_w": (H, F),
                   "experts_down_w": (F, H)})
    else:
        F = cfg["intermediate_size"]
        sh.update({"gate_w": (H, F), "up_w": (H, F), "down_w": (F, H)})
    return sh


def _value(cfg, name, key, shape, dtype):
    if name.endswith("_g"):
        return jnp.ones(shape, dtype)
    scale = 0.1 if name == "router_bias" else cfg["initializer_range"]
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _leaf_key(key, name, layer=None, expert=None):
    k = jax.random.fold_in(key, NAMES.index(name))
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    if expert is not None:
        k = jax.random.fold_in(k, expert)
    return k


def top_leaf(cfg, key, name, dtype):
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    shape = {"wte": (V, H), "normf_g": (H,)}[name]
    return _value(cfg, name, _leaf_key(key, name), shape, dtype)


def is_moe(cfg, layer):
    return layer >= cfg["num_dense_layers"]


def is_conv(cfg, layer):
    return cfg["layer_types"][layer] == CONV


def expert_leaves(cfg, key, layer, expert, dtype):
    """One routed expert's three matrices; ``layer`` is the absolute layer,
    both may be traced."""
    sh = mlp_shapes(cfg, True)
    out = {}
    for n in EXPERT_LEAVES:
        common = jax.random.normal(_leaf_key(key, n, layer), sh[n],
                                   jnp.float32)
        own = jax.random.normal(_leaf_key(key, n, layer, expert), sh[n],
                                jnp.float32)
        x = math.sqrt(1.0 - EXPERT_OWN ** 2) * common + EXPERT_OWN * own
        out[n] = (cfg["initializer_range"] * x).astype(dtype)
    return out


def operator_leaves(cfg, key, layer, conv, dtype):
    """One layer's operator leaves; ``layer`` is the absolute layer (may be
    traced), ``conv`` its kind."""
    return {name: _value(cfg, name, _leaf_key(key, name, layer), shape, dtype)
            for name, shape in operator_shapes(cfg, conv).items()}


def mlp_leaves(cfg, key, layer, moe, dtype, experts=True):
    """One layer's MLP leaves and norms; ``layer`` is the absolute layer
    (may be traced), ``moe`` its kind. With ``experts`` the routed experts
    come stacked ``[experts, ...]``; without, they are left out (the
    reference makes them one at a time)."""
    out = {name: _value(cfg, name, _leaf_key(key, name, layer), shape, dtype)
           for name, shape in mlp_shapes(cfg, moe).items()
           if name not in EXPERT_LEAVES}
    if moe and experts:
        out.update(jax.vmap(
            lambda e: expert_leaves(cfg, key, layer, e, dtype))(
                jnp.arange(cfg["num_experts"])))
    return out


def make_tree(cfg, key, dtype):
    """The whole tree (traceable: call it under one jit)."""
    tree = {n: top_leaf(cfg, key, n, dtype) for n in TOP}
    layers = range(cfg["num_hidden_layers"])
    for stack, moe in (("dense", False), ("moe", True)):
        at = jnp.asarray([l for l in layers if is_moe(cfg, l) == moe],
                         jnp.int32)
        tree[stack] = jax.vmap(
            lambda l, moe=moe: mlp_leaves(cfg, key, l, moe, dtype))(at)
    for stack, conv in (("conv", True), ("attn", False)):
        at = jnp.asarray([l for l in layers if is_conv(cfg, l) == conv],
                         jnp.int32)
        tree[stack] = jax.vmap(
            lambda l, conv=conv: operator_leaves(cfg, key, l, conv, dtype))(
                at)
    return tree


def make_weights(cfg, seed, dtype, out_shardings=None):
    """One jitted call: the tree on the device, in ``dtype``."""
    fn = jax.jit(lambda k: make_tree(cfg, k, jnp.dtype(dtype)),
                 out_shardings=out_shardings)
    return fn(seed_key(seed))
