"""The lfm2 family's plain reference: gated short-convolution layers with a
full attention layer of grouped KV heads among every few, RMS norms on q and
k, a gated FFN in the leading layers and sigmoid-routed experts with no
shared expert in the rest, in straightforward jax.numpy and float32 with
every product through ``mm`` (the harness's ``mm_exact`` at ``highest``
precision for the reference, ``mm_fp8`` for the control; the router's
product too). No kernels, no cache, no state carried from anywhere, no
batching tricks: a convolution runs over its row from zeros before the
sequence, every position attends its causal prefix. It imports nothing of
the program and makes its own weights from the seed (``weights.py``).

Departures from a textbook listing, each for memory only (at 1,536 positions
and 3 rows the [64, T, 1536] expert activations are 0.6 GB a row beside 1.2
GB of logits): rows are walked one at a time (``lax.map``), attention goes
one KV head's group of query heads at a time, only one layer's weights are
alive, and the routed experts are made and applied one at a time (a loop
over the held experts; each meets every token and counts with the token's
weight for it, which is nought where it was not chosen).

The equations, from the published ``config.json`` of ``model_type``
``lfm2_moe`` and the family's published modelling code (what the config's
keys do not settle is in the configuration file's ``assumed``):

* ``h = E[ids]``; every layer: ``h = h + op(rms(h; operator_norm))``, ``h = h
  + mlp(rms(h; ffn_norm))``, RMS norms with gains (``norm_eps``), no biases
  anywhere; after the last layer ``rms(h; embedding_norm)`` and the head,
  which is ``E`` again (tied);
* a ``conv`` layer's operator on ``x [T, H]``: ``[B | C | X] = x W_in``
  (thirds of ``W_in``'s 3H outputs, in that order), ``u = B * X``; ``y_t =
  sum_{j=0..L-1} w[:, j] * u_{t-(L-1)+j}`` for every channel alone
  (depthwise, causal, ``L = conv_L_cache``, ``u`` zero before the sequence,
  no bias); ``out = (C * y) W_out``. No activation;
* a ``full_attention`` layer's operator: ``q = x Wq`` as
  ``num_attention_heads`` heads of ``head_dim``, ``k = x Wk`` and ``v = x
  Wv`` as ``num_key_value_heads`` heads; q and k take an RMS norm over the
  head (one gain each), then are rotated: the pair (i, i + head_dim/2)
  turns by position x ``rope_theta^(-2i/head_dim)``; query head j reads KV
  head ``j // (heads / kv heads)``; scores ``q.k / sqrt(head_dim)``; query i
  sees key j when ``j <= i``; then ``Wo``;
* dense MLP (the leading ``num_dense_layers``): ``down(silu(gate x) * up
  x)`` of ``intermediate_size``;
* expert MLP: ``s = sigmoid(x Wr)``; the ``num_experts_per_tok`` largest of
  ``s + expert_bias``; their weights ``s_e / (sum + 1e-6)``
  (``norm_topk_prob``) times ``routed_scaling_factor``; ``sum w_e E_e(x)``,
  every expert the gated MLP of ``moe_intermediate_size``; no shared
  expert."""
import math

import jax
import jax.numpy as jnp

from benchmark.harness.reference import F32
from benchmark.harness.weights import seed_key

from . import weights as W

ROUTE_NORM_EPS = 1e-6


def rms(x, eps, g):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def ffn(x, gate_w, up_w, down_w, mm):
    return mm(silu(mm(x, gate_w)) * mm(x, up_w), down_w)


# -- the convolution --------------------------------------------------------


def before_sequence(u, rows):
    """The ``rows`` rows of u that lie before position 0: zeros."""
    return jnp.zeros((rows, u.shape[1]), F32)


def tap_positions(T, L):
    """[T, L]: the position whose row of u the j-th tap of position t
    multiplies, ``t - (L - 1) + j`` (below 0: before the sequence)."""
    return jnp.arange(T)[:, None] - (L - 1) + jnp.arange(L)[None, :]


def taps_of(w):
    """The taps [H, L] in the order the sum takes them: oldest row first."""
    return w


def short_conv(u, w):
    """y [T, H]: every channel of u [T, H] convolved alone with its L
    taps."""
    T, L = u.shape[0], w.shape[1]
    rows = jnp.concatenate([before_sequence(u, L - 1), u])    # [L - 1 + T, H]
    at = tap_positions(T, L) + (L - 1)
    w = taps_of(w)
    return sum(w[:, j] * rows[at[:, j]] for j in range(L))


def conv_operator(p, x, cfg, mm):
    """The gated short convolution over one row x [T, H] (already normed)."""
    b, c, xx = jnp.split(mm(x, p["in_w"]), 3, axis=-1)
    return mm(c * short_conv(b * xx, p["taps"]), p["out_w"])


# -- attention --------------------------------------------------------------


def rotate(x, cfg):
    """x [T, heads, d] at positions 0..T-1: the pair (i, i + d/2) turns by
    position x theta^(-2i/d)."""
    T, _, d = x.shape
    half = d // 2
    theta = float(cfg["rope_parameters"]["rope_theta"])
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def head_norm(x, eps, g):
    """The RMS norm of q or k over the head."""
    return rms(x, eps, g)


def kv_head_of(cfg):
    """The KV head that each query head reads."""
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return jnp.arange(cfg["num_attention_heads"]) // group


def attention_operator(p, x, cfg, mm):
    """One attention operator over one row x [T, H] (already normed)."""
    T = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  W.head_dim(cfg))
    eps = cfg["norm_eps"]
    q = rotate(head_norm(mm(x, p["wq"]).reshape(T, nh, d), eps,
                         p["q_norm_g"]), cfg)
    k = rotate(head_norm(mm(x, p["wk"]).reshape(T, nkv, d), eps,
                         p["k_norm_g"]), cfg)
    v = mm(x, p["wv"]).reshape(T, nkv, d)
    # every query head beside the KV head it reads, one KV head's group of
    # query heads at a time
    group = nh // nkv
    order = jnp.argsort(kv_head_of(cfg), stable=True).reshape(nkv, group)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def one_kv_head(args):
        heads, k_h, v_h = args                     # [group], [T, d], [T, d]
        q_h = q[:, heads].transpose(1, 0, 2)       # [group, T, d]
        s = mm(q_h, k_h.T) / math.sqrt(d)          # [group, T, T]
        s = jnp.where(causal, s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), v_h)

    ctx = jax.lax.map(one_kv_head, (order, k.transpose(1, 0, 2),
                                    v.transpose(1, 0, 2)))  # [nkv, g, T, d]
    ctx = jnp.zeros((nh, T, d), F32).at[order.reshape(-1)].set(
        ctx.reshape(nh, T, d))
    return mm(ctx.transpose(1, 0, 2).reshape(T, nh * d), p["wo"])


# -- the MLPs ---------------------------------------------------------------


def weight_scores(s, bias):
    """The scores that the chosen experts' weights are taken from: the
    sigmoids alone; the bias enters the choice only."""
    return s


def route(xn, p, cfg, mm):
    """The weight of every expert for every token [T, E]: nought where the
    expert was not chosen."""
    k, E = cfg["num_experts_per_tok"], cfg["num_experts"]
    s = sigmoid(mm(xn, p["router_w"]))
    _, chosen = jax.lax.top_k(s + p["router_bias"], k)           # [T, k]
    w = jnp.take_along_axis(weight_scores(s, p["router_bias"]), chosen,
                            axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + ROUTE_NORM_EPS)
    w = w * cfg["routed_scaling_factor"]
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

    return jax.lax.fori_loop(lo, hi, one, jnp.zeros_like(xn))


def block(p, op, h, cfg, mm, conv, expert=None):
    """One layer on one row's stream h [T, H]: ``p`` its MLP leaves and
    norms, ``op`` its operator's leaves, ``conv`` the operator's kind;
    ``expert`` None is a dense layer."""
    eps = cfg["norm_eps"]
    x = rms(h, eps, p["operator_norm_g"])
    h = h + (conv_operator(op, x, cfg, mm) if conv
             else attention_operator(op, x, cfg, mm))
    xn = rms(h, eps, p["ffn_norm_g"])
    return h + (ffn(xn, p["gate_w"], p["up_w"], p["down_w"], mm)
                if expert is None else moe(p, xn, cfg, mm, expert))


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
            return W.top_leaf(cfg, key, "wte", dtype).astype(F32)[ids]

        def layer(moe_kind, conv):
            @jax.jit
            def run(key, l, h):
                p = _f32(W.mlp_leaves(cfg, key, l, moe_kind, dtype,
                                      experts=False))
                op = _f32(W.operator_leaves(cfg, key, l, conv, dtype))
                expert = (lambda e: _f32(W.expert_leaves(cfg, key, l, e,
                                                         dtype))) \
                    if moe_kind else None
                return jax.lax.map(
                    lambda x: block(p, op, x, cfg, mm, conv, expert), h)
            return run

        @jax.jit
        def head(key, h):
            g = W.top_leaf(cfg, key, "normf_g", dtype).astype(F32)
            wte = W.top_leaf(cfg, key, "wte", dtype).astype(F32)
            return jax.lax.map(
                lambda x: mm(rms(x, cfg["norm_eps"], g), wte.T), h)

        self.embed, self.head = embed, head
        self.layers = {(m, c): layer(m, c) for m in (False, True)
                       for c in (False, True)}

    def hidden(self, ids):
        h = self.embed(self.key, ids)
        for l in range(self.cfg["num_hidden_layers"]):
            h = self.layers[W.is_moe(self.cfg, l), W.is_conv(self.cfg, l)](
                self.key, jnp.int32(l), h)
        return h


def served_logits(cfg, seed, ids, dtype, mm):
    """float32 logits [n, T, V] of the model whose weights are the seed's,
    stored in ``dtype`` and widened: one full causal forward over ``ids``
    [n, T]."""
    model = _Model(cfg, seed, dtype, mm)
    return model.head(model.key, model.hidden(ids))
