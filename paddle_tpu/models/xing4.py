"""Xing4.0: a decoder with latent (MLA) attention, sigmoid-routed experts
beside a shared expert, and a four-stream residual path (manifold-constrained
hyper-connections, mHC), under the keys of its published ``config.json``.

What is here: the configuration, seeded parameters, the plain forward
(``forward``: no cache, K and V expanded from the latent), the
multi-token-prediction module (``mtp_logits``), and the paged forward that
``serving.Engine`` dispatches (``paged_forward``: one latent row a token a
layer, ``[rms(c_kv) | rope(k_rope)]``, read absorbed) with the seam object
the engine finds through ``Xing4Config.served_model``.

Layer equations (the plain reference, ``benchmark/families/xing4/
reference.py``, states the same independently; tests hold the two together):

* norms are RMS, no biases, FFNs are ``down(silu(gate x) * up x)``;
* attention: ``c_q = rms(x Wqa)``, ``q = c_q Wqb`` -> heads of ``[nope |
  rope]``; ``[c_kv | k_rope] = x Wkva``, ``c_kv = rms(c_kv)``, rotary
  (YaRN) on ``q_rope`` and on the one ``k_rope`` all heads share;
  ``[k_nope | v]`` a head ``= c_kv Wkvb``; scores ``(q_nope.k_nope +
  q_rope.k_rope) * scale``; absorbed, ``q_nope Wkvb_K`` meets ``c_kv``
  directly and ``Wkvb_V`` follows the weighted sum;
* experts: ``s = sigmoid(x Wg)`` in float32, top-k of ``s + bias``, weights
  ``s`` of the chosen over their sum times ``routed_scaling_factor``,
  ``sum w_i E_i(x) + shared(x)``. Dropless: each token meets every held
  expert it chose, in grouped products sized by the traced routing
  (``moe.moe_ffn``), so no token is dropped at any shape;
* mHC around each sublayer ``F`` on the stream ``X`` [n, H]: ``x~ =
  rms(vec X)``, ``Hpre = sigmoid(a_pre x~P_pre + b_pre)``, ``Hpost = 2
  sigmoid(a_post x~P_post + b_post)``, ``Hres = SK(clamp(a_res
  mat(x~P_res) + b_res))``, ``X' = Hres X + Hpost^T (x) F(Hpre X)``.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..serving import metrics
from ..serving.paged_attention import latent_scatter, latent_window
from ..serving.served_model import CacheGeometry, ServedModel
from .moe import F32, HIGHEST, compute_of, ffn, final_logits, layer_leaves, \
    mm, moe_ffn, rms_norm

ROPE_SCALING = (("beta_fast", 32), ("beta_slow", 1), ("factor", 64),
                ("mscale", 1), ("mscale_all_dim", 1),
                ("original_max_position_embeddings", 4096), ("type", "yarn"))


@dataclasses.dataclass(frozen=True)
class Xing4Config:
    """The published keys (defaults: Xing4.0-29B-A4B), hashable so that it
    keys the engine's memoized builders. ``rope_scaling`` is the published
    dict as sorted pairs. Not published: ``experts_held`` (the range of
    routed experts this chip holds; None is all), ``initializer_range``,
    ``compute_dtype``."""
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: tuple = ROPE_SCALING
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    num_nextn_predict_layers: int = 1
    initializer_range: float = 0.02
    compute_dtype: str = "float32"
    experts_held: tuple = None

    @classmethod
    def from_dict(cls, d, **over):
        """From a published ``config.json`` dict (other keys ignored)."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        kw.update(over)
        if isinstance(kw.get("rope_scaling"), dict):
            kw["rope_scaling"] = tuple(sorted(kw["rope_scaling"].items()))
        if kw.get("experts_held") is not None:
            kw["experts_held"] = tuple(kw["experts_held"])
        return cls(**kw)

    # what serving.Engine reads of any model's configuration
    @property
    def max_seq_len(self):
        return self.max_position_embeddings

    @property
    def num_layers(self):
        return self.num_hidden_layers

    @property
    def num_moe_layers(self):
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def held(self):
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def route_norm_eps(self):
        return 1e-20

    @property
    def latent_row(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def served_model(self):
        return SERVED


# ---------------------------------------------------------------------------
# parameters

MHC_SUBLAYERS = ("attn", "ffn")


def _layer_shapes(c, moe):
    H, n, nh = c.hidden_size, c.hc_mult, c.num_attention_heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    sh = {"attn_norm_g": (H,), "wq_a": (H, c.q_lora_rank),
          "q_norm_g": (c.q_lora_rank,), "wq_b": (c.q_lora_rank, nh * qk),
          "wkv_a": (H, c.latent_row), "kv_norm_g": (c.kv_lora_rank,),
          "wkv_b": (c.kv_lora_rank,
                    nh * (c.qk_nope_head_dim + c.v_head_dim)),
          "wo": (nh * c.v_head_dim, H), "ffn_norm_g": (H,)}
    for s in MHC_SUBLAYERS:
        sh[f"hc_{s}_w"] = (n * H, n * (2 + n))
        sh[f"hc_{s}_a"] = (3,)
        sh[f"hc_{s}_b"] = (n * (2 + n),)
    if moe:
        E, F = c.n_routed_experts, c.moe_intermediate_size
        Fs = F * c.n_shared_experts
        sh.update({"router_w": (H, E), "router_bias": (E,),
                   "experts_gate_w": (E, H, F), "experts_up_w": (E, H, F),
                   "experts_down_w": (E, F, H), "shared_gate_w": (H, Fs),
                   "shared_up_w": (H, Fs), "shared_down_w": (Fs, H)})
    else:
        F = c.intermediate_size
        sh.update({"gate_w": (H, F), "up_w": (H, F), "down_w": (F, H)})
    return sh


def _init_leaf(name, shape, key, std):
    if name.endswith("_g"):
        return jnp.ones(shape, F32)
    if name.startswith("hc_") and name.endswith("_a"):      # mHC gains
        return jax.random.uniform(key, shape, F32, 0.5, 1.5)
    if name.startswith("hc_") and name.endswith("_b"):      # mHC biases
        return 0.5 * jax.random.normal(key, shape, F32)
    if name == "router_bias":
        return 0.1 * jax.random.normal(key, shape, F32)
    return std * jax.random.normal(key, shape, F32)


def _init_layers(c, key, moe, layers, dtype):
    out = {}
    for i, (name, shape) in enumerate(sorted(_layer_shapes(c, moe).items())):
        out[name] = _init_leaf(name, (layers,) + shape,
                               jax.random.fold_in(key, i),
                               c.initializer_range).astype(dtype)
    return out


def init_xing4_params(config, key, dtype=F32, mtp=False):
    """The functional tree: ``wte, head_w, normf_g``, ``dense`` and ``moe``
    (each kind's leaves stacked over its layers) and, with ``mtp``, the
    next-token-prediction module."""
    c = config
    H, V = c.hidden_size, c.vocab_size
    k = jax.random.split(key, 6)
    std = c.initializer_range
    tree = {"wte": (std * jax.random.normal(k[0], (V, H), F32)).astype(dtype),
            "head_w": (std * jax.random.normal(k[1], (H, V), F32)
                       ).astype(dtype),
            "normf_g": jnp.ones((H,), dtype),
            "dense": _init_layers(c, k[2], False, c.first_k_dense_replace,
                                  dtype),
            "moe": _init_layers(c, k[3], True, c.num_moe_layers, dtype)}
    if mtp:
        block = _init_layers(c, k[4], True, 1, dtype)
        tree["mtp"] = {
            "enorm_g": jnp.ones((H,), dtype), "hnorm_g": jnp.ones((H,), dtype),
            "eh_proj": (std * jax.random.normal(k[5], (2 * H, H), F32)
                        ).astype(dtype),
            "block": {n: a[0] for n, a in block.items()}}
    return tree


# ---------------------------------------------------------------------------
# pieces


def yarn_inv_freq(config):
    """YaRN's blended inverse frequencies [rope/2] and the gain of cos and
    sin (``mscale`` over ``mscale_all_dim``), as numpy constants."""
    rs = dict(config.rope_scaling)
    dim, base = config.qk_rope_head_dim, float(config.rope_theta)
    factor = float(rs["factor"])
    orig = rs["original_max_position_embeddings"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv = inter * ramp + extra * (1.0 - ramp)
    return inv.astype(np.float32), _yarn_mscale(factor, rs["mscale"]) \
        / _yarn_mscale(factor, rs["mscale_all_dim"])


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(config):
    rs = dict(config.rope_scaling)
    m = _yarn_mscale(float(rs["factor"]), rs["mscale_all_dim"])
    return (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5 * m * m


def rope_cos_sin(config, pos):
    """cos, sin [..., rope/2] (float32) at integer positions ``pos``."""
    inv, gain = yarn_inv_freq(config)
    ang = pos.astype(F32)[..., None] * jnp.asarray(inv)
    return jnp.cos(ang) * gain, jnp.sin(ang) * gain


def apply_rope(x, cos, sin):
    """Rotates the pairs (2i, 2i+1) of x [..., rope]; cos/sin broadcast."""
    xf = x.astype(F32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def sinkhorn(logits, iters, eps):
    """``exp``, then ``iters`` rounds of row then column normalisation
    ([..., n, n], float32): rows and columns come to sum to 1."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


def mhc_maps(p, X, config, which):
    """The three maps of one sublayer from the stream X [B, T, n, H]:
    Hpre, Hpost [B, T, n] and Hres [B, T, n, n], in float32."""
    c = config
    n = c.hc_mult
    B, T = X.shape[:2]
    xt = rms_norm(X.reshape(B, T, -1), None, c.rms_norm_eps)
    proj = jnp.matmul(xt, p[f"hc_{which}_w"].astype(F32), precision=HIGHEST)
    a = p[f"hc_{which}_a"].astype(F32)
    b = p[f"hc_{which}_b"].astype(F32)
    pre = jax.nn.sigmoid(a[0] * proj[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * proj[..., n:2 * n] + b[n:2 * n])
    res = jnp.clip(a[2] * proj[..., 2 * n:] + b[2 * n:],
                   c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max)
    res = sinkhorn(res.reshape(B, T, n, n), c.hc_sinkhorn_iters, c.hc_eps)
    return pre, post, res


def mhc_sublayer(p, X, config, which, fn):
    """``X' = Hres X + Hpost^T (x) fn(Hpre X)`` on the float32 stream X
    [B, T, n, H]; ``fn`` is the pre-normed sublayer on [B, T, H] (it norms
    in float32 and multiplies in the compute type) and returns its float32
    result and a second value, which is passed through."""
    with jax.named_scope("pt_mhc_mix"):
        pre, post, res = mhc_maps(p, X, config, which)
        u = jnp.einsum("btn,btnh->bth", pre, X)
    y, extra = fn(u)
    with jax.named_scope("pt_mhc_mix"):
        out = jnp.einsum("btij,btjh->btih", res, X) \
            + post[..., None] * y.astype(F32)[:, :, None, :]
    return out, extra


def dense_ffn(p, x, config):
    xn = rms_norm(x, p["ffn_norm_g"], config.rms_norm_eps)
    return ffn(xn.astype(compute_of(config)), p["gate_w"], p["up_w"],
               p["down_w"])


def mla_q(p, xn, config, cos, sin):
    """q_nope [B, T, nh, nope] and rotated q_rope [B, T, nh, rope]."""
    c = config
    B, T, _ = xn.shape
    cq = rms_norm(mm(xn, p["wq_a"]), p["q_norm_g"], c.rms_norm_eps)
    q = mm(cq, p["wq_b"]).reshape(B, T, c.num_attention_heads, -1)
    q_nope, q_rope = jnp.split(q, [c.qk_nope_head_dim], axis=-1)
    return q_nope, apply_rope(q_rope, cos[:, :, None], sin[:, :, None])


def mla_latent(p, xn, config, cos, sin):
    """What the cache holds of a token: ``[rms(c_kv) | rope(k_rope)]``
    [B, T, kv_lora_rank + rope]."""
    c = config
    ckv, k_rope = jnp.split(mm(xn, p["wkv_a"]), [c.kv_lora_rank], axis=-1)
    ckv = rms_norm(ckv, p["kv_norm_g"], c.rms_norm_eps)
    return jnp.concatenate([ckv, apply_rope(k_rope, cos, sin)], axis=-1)


def _wkv_b(p, config):
    c = config
    w = p["wkv_b"].reshape(c.kv_lora_rank, c.num_attention_heads,
                           c.qk_nope_head_dim + c.v_head_dim)
    return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]


def mla_attend_expanded(p, q_nope, q_rope, latent, mask, config):
    """The plain form: K and V of every head expanded from the latent rows
    [B, S, row]; mask [B, T, S]. Returns ctx [B, T, nh * v]."""
    c = config
    B, T = q_nope.shape[:2]
    ckv, k_rope = jnp.split(latent, [c.kv_lora_rank], axis=-1)
    wk, wv = _wkv_b(p, c)
    k_nope = jnp.einsum("bsr,rhd->bshd", ckv, wk.astype(ckv.dtype))
    v = jnp.einsum("bsr,rhd->bshd", ckv, wv.astype(ckv.dtype))
    s = jnp.einsum("bthd,bshd->bhts", q_nope.astype(F32), k_nope.astype(F32)) \
        + jnp.einsum("bthd,bsd->bhts", q_rope.astype(F32), k_rope.astype(F32))
    s = jnp.where(mask[:, None], s * softmax_scale(c), -jnp.inf)
    probs = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhts,bshd->bthd", probs, v.astype(F32))
    return ctx.astype(q_nope.dtype).reshape(B, T, -1)


def mla_attend_absorbed(p, q_nope, q_rope, latent, mask, config):
    """The same attention read straight off the latent rows: ``q_nope
    Wkvb_K`` meets ``c_kv``, and ``Wkvb_V`` follows the weighted sum, so no
    K or V of any head is ever made."""
    c = config
    B, T = q_nope.shape[:2]
    ckv, k_rope = jnp.split(latent, [c.kv_lora_rank], axis=-1)
    wk, wv = _wkv_b(p, c)
    q_lat = jnp.einsum("bthd,rhd->bthr", q_nope, wk.astype(q_nope.dtype))
    s = jnp.einsum("bthr,bsr->bhts", q_lat.astype(F32), ckv.astype(F32)) \
        + jnp.einsum("bthd,bsd->bhts", q_rope.astype(F32), k_rope.astype(F32))
    s = jnp.where(mask[:, None], s * softmax_scale(c), -jnp.inf)
    probs = jax.nn.softmax(s, axis=-1)
    ctx_lat = jnp.einsum("bhts,bsr->bthr", probs, ckv.astype(F32))
    ctx = jnp.einsum("bthr,rhd->bthd", ctx_lat.astype(q_nope.dtype),
                     wv.astype(q_nope.dtype))
    return ctx.reshape(B, T, -1)


def _layer(p, X, config, pos, attend, moe, token_mask=None):
    """One layer on the stream X [B, T, n, H]. ``attend(p, q_nope, q_rope,
    latent)`` -> (ctx, carry) is where the plain and the paged forward
    differ. Returns X, attend's carry and the expert statistics (None for a
    dense layer)."""
    c = config
    cos, sin = rope_cos_sin(c, pos)

    def attention(u):
        un = rms_norm(u, p["attn_norm_g"], c.rms_norm_eps).astype(compute_of(c))
        q_nope, q_rope = mla_q(p, un, c, cos, sin)
        ctx, carry = attend(p, q_nope, q_rope, mla_latent(p, un, c, cos, sin))
        return mm(ctx, p["wo"], F32), carry

    X, carry = mhc_sublayer(p, X, c, "attn", attention)
    if moe:
        X, stats = mhc_sublayer(p, X, c, "ffn",
                                lambda u: moe_ffn(p, u, c, token_mask))
    else:
        X, stats = mhc_sublayer(p, X, c, "ffn",
                                lambda u: (dense_ffn(p, u, c), None))
    return X, carry, stats


def _embed(params, config, ids):
    """The stream: the token's embedding repeated into the n rows, float32
    from here to the head (the sublayers multiply in the compute type)."""
    x = params["wte"][ids].astype(F32)
    return jnp.repeat(x[:, :, None, :], config.hc_mult, axis=2)


def _stack_scan(params, X, layer_fn):
    """The leading dense layers, then the stacked expert layers: one scan a
    kind, the absolute layer index beside each layer's leaves (an expert
    layer's expert stacks whole: ``moe.layer_leaves``)."""
    n_dense = params["dense"]["wq_a"].shape[0]
    n_moe = params["moe"]["wq_a"].shape[0]
    with jax.named_scope("pt_layers"):      # the walk's own: moe.run_layers
        X, _ = jax.lax.scan(
            lambda cr, xs: layer_fn(cr, xs, False), X,
            (params["dense"], jnp.arange(n_dense, dtype=jnp.int32)))
        X, _ = jax.lax.scan(
            lambda cr, i: layer_fn(cr, (layer_leaves(params["moe"], i),
                                        n_dense + i), True), X,
            jnp.arange(n_moe, dtype=jnp.int32))
    return X


def _plain_attend(config, mask):
    def attend(p, q_nope, q_rope, latent):
        return mla_attend_expanded(p, q_nope, q_rope, latent, mask,
                                   config), None
    return attend


def forward(params, config, ids, return_hidden=False):
    """The plain causal forward over ids [B, T]: float32 logits [B, T, V]
    (and, with ``return_hidden``, the summed stream before the final norm,
    which the MTP module takes)."""
    B, T = ids.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool))[None], (B, T, T))
    attend = _plain_attend(config, mask)

    def layer_fn(X, xs, moe):
        p_l, _ = xs
        X, _, _ = _layer(p_l, X, config, pos, attend, moe)
        return X, None

    X = _stack_scan(params, _embed(params, config, ids), layer_fn)
    h = jnp.sum(X, axis=2)
    logits = final_logits(params, config, h)
    return (logits, h) if return_hidden else logits


def mtp_logits(params, config, hidden, ids):
    """The multi-token-prediction module: from the main model's summed
    stream ``hidden`` [B, T, H] at positions i and the tokens ids [B, T],
    logits [B, T-1, V] for token i+2: ``h' = Weh [rms(h_i) ;
    rms(emb(t_{i+1}))]``, one expert block, the shared final norm and head."""
    c, m = config, params["mtp"]
    B, T = ids.shape
    emb = params["wte"][ids[:, 1:]].astype(F32)
    cat = jnp.concatenate([rms_norm(hidden[:, :-1], m["hnorm_g"],
                                    c.rms_norm_eps),
                           rms_norm(emb, m["enorm_g"], c.rms_norm_eps)], -1)
    h = mm(cat.astype(compute_of(c)), m["eh_proj"], F32)
    X = jnp.repeat(h[:, :, None, :], c.hc_mult, axis=2)
    pos = jnp.broadcast_to(jnp.arange(T - 1)[None], (B, T - 1))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((T - 1, T - 1), bool))[None],
                            (B, T - 1, T - 1))
    X, _, _ = _layer(m["block"], X, c, pos, _plain_attend(c, mask), True)
    return final_logits(params, c, jnp.sum(X, axis=2))


# ---------------------------------------------------------------------------
# serving: the paged forward over the latent pool, and the engine's seam


def paged_forward(params, config, ids, pools, start, valid, table, page_size):
    """The fused chunk/decode forward the engine dispatches: ids [B, T] is
    each slot's window at positions start[b].. (valid[b] of them real).
    ``pools`` is the one latent pool ``[L, P, page_size, lanes]``: the layer
    scans' carry, written at ``(l, phys, off)`` and gathered at ``[l,
    table]``. Returns logits [B, V] at each slot's last real position, the
    pool, and the expert statistics summed over the expert layers (the
    fullest expert's load as a maximum)."""
    c = config
    (pool,) = pools
    B, T = ids.shape
    pos = start[:, None] + jnp.arange(T)[None, :]
    live = jnp.arange(T)[None, :] < valid[:, None]
    S = table.shape[1] * page_size
    mask = jnp.arange(S)[None, None, :] <= pos[:, :, None]

    def layer_fn(carry, xs, moe):
        X, pool, stats = carry
        p_l, l = xs

        def attend(p, q_nope, q_rope, latent):
            with jax.named_scope("pt_mla_write"):
                new = latent_scatter(pool, l, latent, table, pos, valid,
                                     page_size)
            with jax.named_scope("pt_mla_attend"):
                rows = latent_window(new, l, table, c.latent_row)
                return mla_attend_absorbed(p, q_nope, q_rope, rows, mask,
                                           c), new

        X, pool, st = _layer(p_l, X, c, pos, attend, moe, live)
        if st is not None:
            stats = jnp.concatenate([stats[:2] + st[:2],
                                     jnp.maximum(stats[2:], st[2:])])
        return (X, pool, stats), None

    carry = (_embed(params, c, ids), pool, jnp.zeros((3,), jnp.int32))
    X, pool, stats = _stack_scan(params, carry, layer_fn)
    idx = jnp.maximum(valid - 1, 0)
    last = jnp.take_along_axis(X, idx[:, None, None, None], axis=1)[:, 0]
    h = jnp.sum(last, axis=1)                                    # [B, H]
    return final_logits(params, c, h), (pool,), stats


class _Served(ServedModel):
    """What ``serving.Engine`` asks of this model (``serving/served_model.py``
    states the seam): the cache geometry and the paged forward. What is not
    built for it yet is refused by name at construction."""
    name = "xing4"
    unsupported = frozenset({"spec", "quant", "adapters", "mp",
                             "kv_transfer"})

    def key(self, config):
        return config

    def view(self, key):
        return key

    def prepare(self, params, config):
        tree = {k: v for k, v in params.items() if k != "mtp"}
        return jax.tree_util.tree_map(jnp.asarray, tree)

    def geometry(self, config):
        return CacheGeometry.one_group(("latent",), config.num_hidden_layers,
                                       (config.latent_row,),
                                       config.compute_dtype or "float32")

    def forward(self, params, config, ids, pools, start, valid, table,
                page_size, **_gpt_options):
        return paged_forward(params, config, ids, pools, start, valid, table,
                             page_size)

    def record(self, stats, kind, config):
        metrics.observe_moe(kind, config.num_moe_layers, *stats)


SERVED = _Served()
