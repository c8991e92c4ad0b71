"""The xing4 family's weights, made on the device from ``--seed`` in the type
they are served in. The benchmark makes them and hands them to the program;
the plain reference makes the same values again from the seed, a layer (and
an expert) at a time, and so takes nothing that the program has touched.

``cfg`` is a configuration file's dict under the published key names
(``hidden_size``, ``q_lora_rank``, ``n_routed_experts`` ...).

Layout, the tree the program's entry points take: ``wte [V, H]``,
``head_w [H, V]`` (untied), ``normf_g``, and the layers by kind, each kind's
leaves stacked over its layers: ``dense`` (the leading
``first_k_dense_replace`` layers) and ``moe`` (the rest; expert leaves
``[layers, experts, ...]``). ``mtp`` (the next-token-prediction module: one
expert block unstacked, two norms, the joining projection) is made only where
asked for: the engine does not load it.

Values (the configuration's ``assumed`` lists them): every matrix
N(0, ``initializer_range``); norm gains 1; of each mHC map the three gains
``hc_*_a`` uniform in [0.5, 1.5) and the biases ``hc_*_b`` N(0, 0.5); the
router's selection bias N(0, 0.1). A routed expert's matrix is
``sqrt(1 - EXPERT_OWN^2)`` of a matrix that its layer's experts share plus
``EXPERT_OWN`` of one that is its own (both N(0, 1), so the sum is too):
experts as sparse upcycling leaves them (Komatsuzaki et al. 2022,
arXiv:2212.05055: every expert starts as a copy of one FFN). Every value is a
function of (seed, leaf, absolute layer, expert), so any slice can be made
again alone.

Why ``EXPERT_OWN``: where rounding flips a token's fourth expert against its
fifth (the stated bfloat16 does, in about one token-layer of a hundred), two
independent seeded experts swap one arbitrary vector for another, and one
flip moved a served token's logit as far as the float8 control moves it
(PERF.md, PR 28: 3.3 against 3.1, no limit fits between). A trained router's
near-tie lies between experts that serve the token about alike; these do.
The routed experts' part keeps its whole size beside the shared expert's,
so a wrong routing weight or scale, or a dropped token, moves the logits as
far as a dropped shared expert does (``faults.py``); which of two near-alike
experts was picked is resolved only as far as they differ (PERF.md section 7)."""
import math

import jax
import jax.numpy as jnp

from benchmark.harness.weights import seed_key

TOP = ("wte", "head_w", "normf_g")
MHC = ("attn", "ffn")
EXPERT_LEAVES = ("experts_gate_w", "experts_up_w", "experts_down_w")
MTP_LAYER = 1 << 20          # the MTP block's "layer" in the leaf keys
EXPERT_OWN = 0.1             # share of a routed expert's matrix that is its own


def layer_shapes(cfg, moe):
    """{leaf: shape} of one layer of a kind; an expert leaf's shape is one
    expert's."""
    H, n, nh = cfg["hidden_size"], cfg["hc_mult"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    sh = {"attn_norm_g": (H,), "wq_a": (H, cfg["q_lora_rank"]),
          "q_norm_g": (cfg["q_lora_rank"],),
          "wq_b": (cfg["q_lora_rank"], nh * qk), "wkv_a": (H, row),
          "kv_norm_g": (cfg["kv_lora_rank"],),
          "wkv_b": (cfg["kv_lora_rank"],
                    nh * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])),
          "wo": (nh * cfg["v_head_dim"], H), "ffn_norm_g": (H,)}
    for s in MHC:
        sh[f"hc_{s}_w"] = (n * H, n * (2 + n))
        sh[f"hc_{s}_a"] = (3,)
        sh[f"hc_{s}_b"] = (n * (2 + n),)
    if moe:
        F = cfg["moe_intermediate_size"]
        Fs = F * cfg["n_shared_experts"]
        sh.update({"router_w": (H, cfg["n_routed_experts"]),
                   "router_bias": (cfg["n_routed_experts"],),
                   "experts_gate_w": (H, F), "experts_up_w": (H, F),
                   "experts_down_w": (F, H), "shared_gate_w": (H, Fs),
                   "shared_up_w": (H, Fs), "shared_down_w": (Fs, H)})
    else:
        F = cfg["intermediate_size"]
        sh.update({"gate_w": (H, F), "up_w": (H, F), "down_w": (F, H)})
    return sh


MTP_TOP = ("eh_proj", "enorm_g", "hnorm_g")
# every leaf's place in the seed's keys: append, never reorder
NAMES = TOP + MTP_TOP + (
    "attn_norm_g", "wq_a", "q_norm_g", "wq_b", "wkv_a", "kv_norm_g", "wkv_b",
    "wo", "ffn_norm_g", "hc_attn_w", "hc_attn_a", "hc_attn_b", "hc_ffn_w",
    "hc_ffn_a", "hc_ffn_b", "gate_w", "up_w", "down_w", "router_w",
    "router_bias", "experts_gate_w", "experts_up_w", "experts_down_w",
    "shared_gate_w", "shared_up_w", "shared_down_w")


def _value(cfg, name, key, shape, dtype):
    if name.endswith("_g"):
        return jnp.ones(shape, dtype)
    if name.startswith("hc_") and name.endswith("_a"):
        x = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
    elif name.startswith("hc_") and name.endswith("_b"):
        x = 0.5 * jax.random.normal(key, shape, jnp.float32)
    elif name == "router_bias":
        x = 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        x = cfg["initializer_range"] * jax.random.normal(key, shape,
                                                         jnp.float32)
    return x.astype(dtype)


def _leaf_key(key, name, layer=None, expert=None):
    k = jax.random.fold_in(key, NAMES.index(name))
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    if expert is not None:
        k = jax.random.fold_in(k, expert)
    return k


def top_leaf(cfg, key, name, dtype):
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    shape = {"wte": (V, H), "head_w": (H, V), "normf_g": (H,),
             "eh_proj": (2 * H, H), "enorm_g": (H,), "hnorm_g": (H,)}[name]
    return _value(cfg, name, _leaf_key(key, name), shape, dtype)


def is_moe(cfg, layer):
    return layer >= cfg["first_k_dense_replace"]


def expert_leaves(cfg, key, layer, expert, dtype):
    """One routed expert's three matrices; ``layer`` is the absolute layer,
    both may be traced."""
    sh = layer_shapes(cfg, True)
    out = {}
    for n in EXPERT_LEAVES:
        common = jax.random.normal(_leaf_key(key, n, layer), sh[n],
                                   jnp.float32)
        own = jax.random.normal(_leaf_key(key, n, layer, expert), sh[n],
                                jnp.float32)
        x = math.sqrt(1.0 - EXPERT_OWN ** 2) * common + EXPERT_OWN * own
        out[n] = (cfg["initializer_range"] * x).astype(dtype)
    return out


def layer_leaves(cfg, key, layer, moe, dtype, experts=True):
    """One layer's leaves; ``layer`` is the absolute layer (may be traced),
    ``moe`` its kind. With ``experts`` the routed experts come stacked
    ``[experts, ...]``; without, they are left out (the reference makes them
    one at a time)."""
    out = {}
    for name, shape in layer_shapes(cfg, moe).items():
        if name in EXPERT_LEAVES:
            continue
        out[name] = _value(cfg, name, _leaf_key(key, name, layer), shape,
                           dtype)
    if moe and experts:
        out.update(jax.vmap(
            lambda e: expert_leaves(cfg, key, layer, e, dtype))(
                jnp.arange(cfg["n_routed_experts"])))
    return out


def mtp_leaves(cfg, key, dtype, experts=True):
    return {"enorm_g": top_leaf(cfg, key, "enorm_g", dtype),
            "hnorm_g": top_leaf(cfg, key, "hnorm_g", dtype),
            "eh_proj": top_leaf(cfg, key, "eh_proj", dtype),
            "block": layer_leaves(cfg, key, MTP_LAYER, True, dtype, experts)}


def make_tree(cfg, key, dtype, mtp=False):
    """The whole tree (traceable: call it under one jit)."""
    tree = {n: top_leaf(cfg, key, n, dtype) for n in TOP}
    nd = cfg["first_k_dense_replace"]
    tree["dense"] = jax.vmap(
        lambda l: layer_leaves(cfg, key, l, False, dtype))(jnp.arange(nd))
    tree["moe"] = jax.vmap(
        lambda l: layer_leaves(cfg, key, l, True, dtype))(
            jnp.arange(nd, cfg["num_hidden_layers"]))
    if mtp:
        tree["mtp"] = mtp_leaves(cfg, key, dtype)
    return tree


def make_weights(cfg, seed, dtype, out_shardings=None, mtp=False):
    """One jitted call: the tree on the device, in ``dtype``."""
    fn = jax.jit(lambda k: make_tree(cfg, k, jnp.dtype(dtype), mtp),
                 out_shardings=out_shardings)
    return fn(seed_key(seed))
