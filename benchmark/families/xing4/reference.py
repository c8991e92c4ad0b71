"""The xing4 family's plain reference: latent (MLA) attention with YaRN
rotary positions, sigmoid-routed experts beside a shared expert, and the
four-stream mHC residual path, in straightforward jax.numpy and float32 with
every product through ``mm`` (the harness's ``mm_exact`` at ``highest``
precision for the reference, ``mm_fp8`` for the control; the router's and
the mHC maps' products too). No kernels, no cache, no batching tricks: K and
V of every head are expanded from the latent and every position attends its
whole causal prefix. It imports nothing of the program and makes its own
weights from the seed (``weights.py``).

Departures from a textbook listing, each for memory only: rows are walked one
at a time (``lax.map``) so that a row's [heads, T, T] scores fit, only one
layer's weights are alive, and the routed experts are made and applied one at
a time (a loop over the held experts; each meets every token and counts with
the token's weight for it, which is nought where it was not chosen).

The equations, from the published ``config.json`` (what it does not settle is
in the configuration file's ``assumed``):

* RMS norms (``rms_norm_eps``), no biases, FFN ``down(silu(gate x) * up x)``;
* ``c_q = rms(x Wqa)``, ``q = c_q Wqb`` -> heads of ``[q_nope | q_rope]``;
  ``[c_kv | k_rope] = x Wkva``, ``c_kv = rms(c_kv)``; rotary on ``q_rope``
  and on the one ``k_rope`` that all heads share, pairs (2i, 2i+1), YaRN's
  blended frequencies; ``[k_nope | v]`` a head ``= c_kv Wkvb``; scores
  ``(q_nope.k_nope + q_rope.k_rope) * (nope + rope)^-0.5 * m^2``, ``m = 0.1
  mscale_all_dim ln(factor) + 1``; causal softmax; ``Wo``;
* ``s = sigmoid(x Wg)``, the ``num_experts_per_tok`` largest of ``s + bias``,
  weights ``s`` of the chosen over their sum times ``routed_scaling_factor``;
  ``sum w_i E_i(x) + shared(x)``;
* mHC around each sublayer ``F`` (attention, then the FFN) on the stream X
  [n, H]: ``x~ = rms(vec X)`` (no gain), ``Hpre = sigmoid(a_pre x~P_pre +
  b_pre)``, ``Hpost = 2 sigmoid(a_post x~P_post + b_post)``, ``Hres =
  SK(clamp(a_res mat(x~P_res) + b_res))`` with ``SK``: ``exp``, then
  ``hc_sinkhorn_iters`` rounds of row then column normalisation (``hc_eps``
  in the denominators); ``X' = Hres X + Hpost^T (x) F(Hpre X)``. The
  embedding is repeated into the n rows; the rows are summed before the
  final norm;
* MTP: ``h' = Weh [rms(h_i) ; rms(emb(t_{i+1}))]``, one expert block, the
  shared final norm and head: logits for ``t_{i+2}``."""
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness.reference import F32
from benchmark.harness.weights import seed_key

from . import weights as W


def rms(x, eps, g=None):
    y = x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y if g is None else y * g


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def ffn(x, gate_w, up_w, down_w, mm):
    return mm(silu(mm(x, gate_w)) * mm(x, up_w), down_w)


# ---------------------------------------------------------------------------
# rotary positions (YaRN)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(cfg):
    """Inverse frequencies [rope/2]: the original ones where a dimension
    turns more than ``beta_fast`` times over the original context, those
    divided by ``factor`` where it turns fewer than ``beta_slow`` times, a
    linear blend between. And the gain of cos and sin."""
    rs = cfg["rope_scaling"]
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    orig = rs["original_max_position_embeddings"]

    def dim_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    plain = base ** (-2.0 * i / dim)
    blend = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = plain * (1.0 - blend) + plain / rs["factor"] * blend
    gain = _mscale(rs["factor"], rs["mscale"]) \
        / _mscale(rs["factor"], rs["mscale_all_dim"])
    return jnp.asarray(inv, F32), gain


def rotary_cos_sin(x, cfg):
    """cos and sin [T, 1.., rope/2] for x [T, ..., rope] at positions
    0..T-1, frequency i along the last axis."""
    inv, gain = yarn_frequencies(cfg)
    T = x.shape[0]
    ang = jnp.arange(T, dtype=F32)[:, None] * inv
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (-1,))
    return jnp.cos(ang) * gain, jnp.sin(ang) * gain


def rotate(x, cfg):
    """x [T, ..., rope] at positions 0..T-1: the pair (2i, 2i+1) turns by
    position x frequency i."""
    cos, sin = rotary_cos_sin(x, cfg)
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def softmax_scale(cfg):
    rs = cfg["rope_scaling"]
    m = _mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


# ---------------------------------------------------------------------------
# one row [T, ...] at a time


def attention(p, x, cfg, mm):
    """Latent attention over one row x [T, H] (pre-normed inside)."""
    T = x.shape[0]
    nh = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    xn = rms(x, eps, p["attn_norm_g"])
    cq = rms(mm(xn, p["wq_a"]), eps, p["q_norm_g"])
    q = mm(cq, p["wq_b"]).reshape(T, nh, nope + rope)
    kv = mm(xn, p["wkv_a"])
    ckv = rms(kv[:, :rank], eps, p["kv_norm_g"])
    k_rope = rotate(kv[:, rank:], cfg)                           # [T, rope]
    kvb = mm(ckv, p["wkv_b"]).reshape(T, nh, nope + cfg["v_head_dim"])
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], cfg)], -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_rope[:, None], (T, nh, rope))], -1)
    v = kvb[..., nope:]
    s = mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) * softmax_scale(cfg)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)                               # [nh, T, T]
    ctx = mm(a, v.transpose(1, 0, 2)).transpose(1, 0, 2).reshape(T, -1)
    return mm(ctx, p["wo"])


def route(xn, p, cfg, mm):
    """The weight of every expert for every token [T, E]: nought where the
    expert was not chosen."""
    k, E = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    s = sigmoid(mm(xn, p["router_w"]))
    _, chosen = jax.lax.top_k(s + p["router_bias"], k)           # [T, k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(chosen, E, dtype=F32) * w[..., None], axis=1)


def moe(p, x, cfg, mm, expert):
    """The expert layer's FFN over one row x [T, H]. ``expert(e)`` gives
    expert e's three matrices in float32; the held experts (``experts_held``,
    default all) are applied one at a time."""
    xn = rms(x, cfg["rms_norm_eps"], p["ffn_norm_g"])
    weight = route(xn, p, cfg, mm)
    lo, hi = cfg.get("experts_held") or (0, cfg["n_routed_experts"])

    def one(e, acc):
        w = expert(e)
        y = ffn(xn, w["experts_gate_w"], w["experts_up_w"],
                w["experts_down_w"], mm)
        return acc + jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1) * y

    out = jax.lax.fori_loop(lo, hi, one, jnp.zeros_like(x))
    return out + ffn(xn, p["shared_gate_w"], p["shared_up_w"],
                     p["shared_down_w"], mm)


def dense(p, x, cfg, mm):
    xn = rms(x, cfg["rms_norm_eps"], p["ffn_norm_g"])
    return ffn(xn, p["gate_w"], p["up_w"], p["down_w"], mm)


def sinkhorn(logits, iters, eps):
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)       # rows
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)       # columns
    return m


def hyper_connect(p, X, which, fn, cfg, mm):
    """One mHC-wrapped sublayer on the stream X [T, n, H]."""
    T, n, H = X.shape
    proj = mm(rms(X.reshape(T, n * H), cfg["rms_norm_eps"]),
              p[f"hc_{which}_w"])                                # [T, n(2+n)]
    a, b = p[f"hc_{which}_a"], p[f"hc_{which}_b"]
    pre = sigmoid(a[0] * proj[:, :n] + b[:n])
    post = 2.0 * sigmoid(a[1] * proj[:, n:2 * n] + b[n:2 * n])
    res = jnp.clip(a[2] * proj[:, 2 * n:] + b[2 * n:],
                   cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    res = sinkhorn(res.reshape(T, n, n), cfg["hc_sinkhorn_iters"],
                   cfg["hc_eps"])
    y = fn(jnp.sum(pre[:, :, None] * X, axis=1))                 # [T, H]
    mixed = jnp.sum(res[:, :, :, None] * X[:, None, :, :], axis=2)
    return mixed + post[:, :, None] * y[:, None, :]


def block(p, X, cfg, mm, expert=None):
    """One layer on one row's stream X [T, n, H]; ``expert`` None is a
    dense layer."""
    X = hyper_connect(p, X, "attn", lambda u: attention(p, u, cfg, mm),
                      cfg, mm)
    if expert is None:
        return hyper_connect(p, X, "ffn", lambda u: dense(p, u, cfg, mm),
                             cfg, mm)
    return hyper_connect(p, X, "ffn", lambda u: moe(p, u, cfg, mm, expert),
                         cfg, mm)


# ---------------------------------------------------------------------------
# the model over rows ids [n, T]


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


class _Model:
    """The jitted pieces, each making its own weights from the seed's key."""

    def __init__(self, cfg, seed, dtype, mm):
        self.cfg, self.key = cfg, seed_key(seed)
        dtype = jnp.dtype(dtype)
        n = cfg["hc_mult"]

        @jax.jit
        def embed(key, ids):
            wte = W.top_leaf(cfg, key, "wte", dtype).astype(F32)
            return jnp.repeat(wte[ids][:, :, None, :], n, axis=2)

        def layer(moe_kind):
            @jax.jit
            def run(key, l, X):
                p = _f32(W.layer_leaves(cfg, key, l, moe_kind, dtype,
                                        experts=False))
                expert = (lambda e: _f32(W.expert_leaves(cfg, key, l, e,
                                                         dtype))) \
                    if moe_kind else None
                return jax.lax.map(lambda x: block(p, x, cfg, mm, expert), X)
            return run

        @jax.jit
        def head(key, h):
            g = W.top_leaf(cfg, key, "normf_g", dtype).astype(F32)
            hw = W.top_leaf(cfg, key, "head_w", dtype).astype(F32)
            return jax.lax.map(
                lambda x: mm(rms(x, cfg["rms_norm_eps"], g), hw), h)

        @jax.jit
        def mtp_join(key, h, ids):
            m = _f32({k: W.top_leaf(cfg, key, k, dtype)
                      for k in W.MTP_TOP + ("wte",)})
            eps = cfg["rms_norm_eps"]
            cat = jnp.concatenate(
                [rms(h[:, :-1], eps, m["hnorm_g"]),
                 rms(m["wte"][ids[:, 1:]], eps, m["enorm_g"])], axis=-1)
            return jnp.repeat(mm(cat, m["eh_proj"])[:, :, None, :], n, axis=2)

        self.embed, self.head, self.mtp_join = embed, head, mtp_join
        self.dense_layer, self.moe_layer = layer(False), layer(True)

    def hidden(self, ids):
        """The summed stream [n, T, H] before the final norm."""
        X = self.embed(self.key, ids)
        for l in range(self.cfg["num_hidden_layers"]):
            fn = self.moe_layer if W.is_moe(self.cfg, l) else self.dense_layer
            X = fn(self.key, jnp.int32(l), X)
        return jnp.sum(X, axis=2)


def served_logits(cfg, seed, ids, dtype, mm):
    """float32 logits [n, T, V] of the model whose weights are the seed's,
    stored in ``dtype`` and widened: one full causal forward over ``ids``
    [n, T]."""
    model = _Model(cfg, seed, dtype, mm)
    return model.head(model.key, model.hidden(ids))


def mtp_logits(cfg, seed, ids, dtype, mm):
    """float32 logits [n, T-1, V] of the multi-token-prediction module:
    position i (from the main model's stream at i and token i+1) predicts
    token i+2."""
    model = _Model(cfg, seed, dtype, mm)
    X = model.mtp_join(model.key, model.hidden(ids), ids)
    X = model.moe_layer(model.key, jnp.int32(W.MTP_LAYER), X)
    return model.head(model.key, jnp.sum(X, axis=2))
