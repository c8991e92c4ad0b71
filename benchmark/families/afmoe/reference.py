"""The afmoe family's plain reference: window and full attention layers with
grouped KV heads, a sigmoid gate on the context, RMS norms on q and k,
sandwich norms, muP's embedding scale, and sigmoid-routed experts beside a
shared expert, in straightforward jax.numpy and float32 with every product
through ``mm`` (the harness's ``mm_exact`` at ``highest`` precision for the
reference, ``mm_fp8`` for the control; the router's product too). No
kernels, no cache, no ring, no batching tricks: every position attends its
causal prefix, cut to the window in a window layer by a mask. It imports
nothing of the program and makes its own weights from the seed
(``weights.py``).

Departures from a textbook listing, each for memory only (at 6,400 positions
the [32, T, T] scores are 5.2 GB and the [128, T, 1024] expert activations
3.4 GB beside 5.1 GB of logits): rows are walked one at a time
(``lax.map``), attention goes one KV head's group of query heads and one
block of query rows at a time, only one layer's weights are alive, and the
routed experts are made and applied one at a time (a loop over the held
experts; each meets every token and counts with the token's weight for it,
which is nought where it was not chosen).

The equations, from the published ``config.json`` and the published
modelling code of ``model_type`` ``afmoe`` (what the config's keys do not
settle is in the configuration file's ``assumed``):

* ``h = E[ids] * sqrt(hidden_size)`` (``mup_enabled``);
* every layer: ``h = h + post_attn_norm(attn(attn_norm(h)))``, ``h = h +
  post_ffn_norm(mlp(ffn_norm(h)))``, RMS norms with gains
  (``rms_norm_eps``), no biases anywhere;
* attention on ``x``: ``q = x Wq`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k = x Wk`` and ``v = x Wv`` as ``num_key_value_heads``
  heads, ``g = x Wg``; q and k take an RMS norm over the head (one gain
  each); in a ``sliding_attention`` layer, and only there, q and k are
  rotated: the pair (i, i + head_dim/2) turns by position x
  ``rope_theta^(-2i/head_dim)``; query head j reads KV head ``j // (heads /
  kv heads)``; scores ``q.k / sqrt(head_dim)``; query i sees key j when ``j
  <= i`` and, in a window layer, ``i - j < sliding_window``; the context is
  multiplied by ``sigmoid(g)``, then by ``Wo``;
* dense MLP (the leading ``num_dense_layers``): ``down(silu(gate x) * up
  x)`` of ``intermediate_size``;
* expert MLP: ``s = sigmoid(x Wr)``; the ``num_experts_per_tok`` largest of
  ``s + bias``; their weights ``s_e / (sum + 1e-20)`` (``route_norm``) times
  ``route_scale``; ``shared(x) + sum w_e E_e(x)``, every expert the gated
  MLP of ``moe_intermediate_size`` (the shared one ``num_shared_experts``
  times as wide);
* final RMS norm, untied head."""
import math

import jax
import jax.numpy as jnp

from benchmark.harness.reference import F32
from benchmark.harness.weights import seed_key

from . import weights as W

ROW_BLOCK = 1024         # query rows of one block of attention, at most
ROTATED = ("sliding_attention",)      # the layer types whose q and k turn


def rms(x, eps, g):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def ffn(x, gate_w, up_w, down_w, mm):
    return mm(silu(mm(x, gate_w)) * mm(x, up_w), down_w)


def rotate(x, cfg):
    """x [T, heads, d] at positions 0..T-1: the pair (i, i + d/2) turns by
    position x theta^(-2i/d)."""
    T, _, d = x.shape
    half = d // 2
    inv = float(cfg["rope_theta"]) ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def sees(i, j, layer_type, cfg):
    """Whether query position i sees key position j in a layer of this
    type."""
    if layer_type == "sliding_attention":
        return (j <= i) & (i - j < cfg["sliding_window"])
    return j <= i


def kv_head_of(cfg):
    """The KV head that each query head reads."""
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return jnp.arange(cfg["num_attention_heads"]) // group


def gate(ctx, g):
    return ctx * sigmoid(g)


def _row_block(T):
    return max(b for b in range(1, min(T, ROW_BLOCK) + 1) if T % b == 0)


def attention(p, x, cfg, mm, layer_type):
    """One attention sublayer over one row x [T, H] (already normed)."""
    T = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = rms(mm(x, p["wq"]).reshape(T, nh, d), eps, p["q_norm_g"])
    k = rms(mm(x, p["wk"]).reshape(T, nkv, d), eps, p["k_norm_g"])
    v = mm(x, p["wv"]).reshape(T, nkv, d)
    if layer_type in ROTATED:
        q, k = rotate(q, cfg), rotate(k, cfg)
    # every query head beside the KV head it reads, one KV head's group of
    # query heads and one block of query rows at a time
    group = nh // nkv
    order = jnp.argsort(kv_head_of(cfg), stable=True).reshape(nkv, group)
    rows = _row_block(T)
    j = jnp.arange(T)

    def one_kv_head(args):
        heads, k_h, v_h = args                     # [group], [T, d], [T, d]
        q_h = q[:, heads].transpose(1, 0, 2)       # [group, T, d]

        def one_block(r):
            i = r * rows + jnp.arange(rows)
            qb = jax.lax.dynamic_slice_in_dim(q_h, r * rows, rows, axis=1)
            s = mm(qb, k_h.T) / math.sqrt(d)       # [group, rows, T]
            s = jnp.where(sees(i[:, None], j[None, :], layer_type, cfg), s,
                          -jnp.inf)
            return mm(jax.nn.softmax(s, axis=-1), v_h)

        out = jax.lax.map(one_block, jnp.arange(T // rows))
        return out.transpose(1, 0, 2, 3).reshape(group, T, d)

    ctx = jax.lax.map(one_kv_head, (order, k.transpose(1, 0, 2),
                                    v.transpose(1, 0, 2)))  # [nkv, g, T, d]
    ctx = jnp.zeros((nh, T, d), F32).at[order.reshape(-1)].set(
        ctx.reshape(nh, T, d))
    ctx = ctx.transpose(1, 0, 2).reshape(T, nh * d)
    return mm(gate(ctx, mm(x, p["wg"])), p["wo"])


def route(xn, p, cfg, mm):
    """The weight of every expert for every token [T, E]: nought where the
    expert was not chosen."""
    k, E = cfg["num_experts_per_tok"], cfg["num_experts"]
    s = sigmoid(mm(xn, p["router_w"]))
    _, chosen = jax.lax.top_k(s + p["router_bias"], k)           # [T, k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["route_norm"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * cfg["route_scale"]
    return jnp.sum(jax.nn.one_hot(chosen, E, dtype=F32) * w[..., None], axis=1)


def moe(p, xn, cfg, mm, expert):
    """The expert MLP over one row xn [T, H] (already normed).
    ``expert(e)`` gives expert e's three matrices in float32; the held
    experts (``experts_held``, default all) are applied one at a time."""
    weight = route(xn, p, cfg, mm)
    lo, hi = cfg.get("experts_held") or (0, cfg["num_experts"])

    def one(e, acc):
        w = expert(e)
        y = ffn(xn, w["experts_gate_w"], w["experts_up_w"],
                w["experts_down_w"], mm)
        return acc + jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1) * y

    out = jax.lax.fori_loop(lo, hi, one, jnp.zeros_like(xn))
    return out + ffn(xn, p["shared_gate_w"], p["shared_up_w"],
                     p["shared_down_w"], mm)


def block(p, h, cfg, mm, layer_type, expert=None):
    """One layer on one row's stream h [T, H]; ``expert`` None is a dense
    layer."""
    eps = cfg["rms_norm_eps"]
    a = attention(p, rms(h, eps, p["attn_norm_g"]), cfg, mm, layer_type)
    h = h + rms(a, eps, p["attn_post_norm_g"])
    xn = rms(h, eps, p["ffn_norm_g"])
    y = ffn(xn, p["gate_w"], p["up_w"], p["down_w"], mm) if expert is None \
        else moe(p, xn, cfg, mm, expert)
    return h + rms(y, eps, p["ffn_post_norm_g"])


# ---------------------------------------------------------------------------
# the model over rows ids [n, T]


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


class _Model:
    """The jitted pieces, each making its own weights from the seed's key."""

    def __init__(self, cfg, seed, dtype, mm):
        self.cfg, self.key = cfg, seed_key(seed)
        dtype = jnp.dtype(dtype)

        @jax.jit
        def embed(key, ids):
            wte = W.top_leaf(cfg, key, "wte", dtype).astype(F32)
            scale = math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] \
                else 1.0
            return wte[ids] * scale

        def layer(moe_kind, layer_type):
            @jax.jit
            def run(key, l, h):
                p = _f32(W.layer_leaves(cfg, key, l, moe_kind, dtype,
                                        experts=False))
                expert = (lambda e: _f32(W.expert_leaves(cfg, key, l, e,
                                                         dtype))) \
                    if moe_kind else None
                return jax.lax.map(
                    lambda x: block(p, x, cfg, mm, layer_type, expert), h)
            return run

        @jax.jit
        def head(key, h):
            g = W.top_leaf(cfg, key, "normf_g", dtype).astype(F32)
            hw = W.top_leaf(cfg, key, "head_w", dtype).astype(F32)
            return jax.lax.map(
                lambda x: mm(rms(x, cfg["rms_norm_eps"], g), hw), h)

        self.embed, self.head = embed, head
        self.layers = {(m, t): layer(m, t) for m in (False, True)
                       for t in set(cfg["layer_types"])}

    def hidden(self, ids):
        h = self.embed(self.key, ids)
        for l, t in enumerate(self.cfg["layer_types"]):
            h = self.layers[W.is_moe(self.cfg, l), t](self.key, jnp.int32(l),
                                                      h)
        return h


def served_logits(cfg, seed, ids, dtype, mm):
    """float32 logits [n, T, V] of the model whose weights are the seed's,
    stored in ``dtype`` and widened: one full causal forward over ``ids``
    [n, T]."""
    model = _Model(cfg, seed, dtype, mm)
    return model.head(model.key, model.hidden(ids))
