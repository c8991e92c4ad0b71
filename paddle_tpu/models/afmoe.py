"""AFMoE (``model_type`` ``afmoe``: Arcee's Trinity family): a decoder whose
layers attend either to a sliding window or to the whole context
(``layer_types``), with grouped KV heads, a sigmoid gate on the attention's
context, RMS norms on q and k a head, sandwich norms, muP's embedding scale,
and sigmoid-routed experts beside a shared expert, under the keys of its
published ``config.json``.

What is here: the configuration, seeded parameters, the plain forward
(``forward``: no cache), and the paged forward that ``serving.Engine``
dispatches (``paged_forward``) with the seam object the engine finds through
``AfmoeConfig.served_model``. The cache has two groups of layers: the full
layers' pools hold a request's whole context, the window layers' a ring a
slot (``serving/served_model.py``).

Layer equations (the plain reference, ``benchmark/families/afmoe/
reference.py``, states the same independently; tests hold the two together):

* ``h = E[ids] * sqrt(hidden_size)`` (``mup_enabled``); every layer ``h = h
  + post_attn_norm(attn(attn_norm(h)))``, ``h = h + post_ffn_norm(mlp(
  ffn_norm(h)))``: four RMS norms with gains; final RMS norm, untied head;
* attention on ``x``: ``q = x Wq`` as ``num_attention_heads`` heads, ``k = x
  Wk`` and ``v = x Wv`` as ``num_key_value_heads`` heads, ``g = x Wg``; q and
  k take an RMS norm over the head (one gain each, shared by the heads); in a
  ``sliding_attention`` layer, and only there, q and k are rotated
  (``rope_theta``, the whole head, pairs ``(i, i + head_dim / 2)``); a
  ``full_attention`` layer has no positional signal. Query head j reads KV
  head ``j // (heads / kv heads)``. Query i sees key j when ``j <= i`` and,
  in a window layer, ``i - j < sliding_window``. The context is multiplied
  by ``sigmoid(g)``, then by ``Wo``;
* the leading ``num_dense_layers`` have a gated FFN of ``intermediate_size``,
  the others the expert layer of ``models/moe.py``: ``num_experts`` experts
  of ``moe_intermediate_size``, the ``num_experts_per_tok`` largest of
  ``sigmoid(x Wr) + bias``, weights normalised (``route_norm``) and scaled
  (``route_scale``), beside ``num_shared_experts`` shared ones, dropless.

The layers' leaves are stacked by the kind of their MLP (``dense``, ``moe``);
a layer's attention kind comes from ``layer_types[l]``. ``layer_plan`` turns
the sequence of kinds into a few segments, each one pattern of layers
repeated (the published 2 + 30 layers: two dense window layers, then seven
periods of window, full, window, window, then two more), and each segment
is ONE scan over its repeats: the depth is a number, not another program.
"""
from __future__ import annotations

import dataclasses
import logging
import math

import jax
import jax.numpy as jnp

from ..serving import metrics
from ..serving.paged_attention import grouped_attend, paged_attention_read, \
    paged_kv_scatter, window_mask
from ..serving.served_model import CacheGeometry, CacheGroup, ServedModel
from .moe import F32, compute_of, ffn, final_logits, layer_plan, mm, \
    moe_ffn, rms_norm, rotate, run_layers

logger = logging.getLogger("paddle_tpu.afmoe")

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """The published keys (defaults: Trinity-Mini), hashable so that it keys
    the engine's memoized builders; ``layer_types`` a tuple, None is full
    attention every ``global_attn_every_n_layers``-th layer. Not published:
    ``experts_held`` (the range of routed experts this chip holds; None is
    all), ``initializer_range``, ``compute_dtype``."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_experts: int = 128
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    route_norm: bool = True
    route_scale: float = 2.826
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    layer_types: tuple = None
    global_attn_every_n_layers: int = 4
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    initializer_range: float = 0.02
    compute_dtype: str = "float32"
    experts_held: tuple = None

    def __post_init__(self):
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            object.__setattr__(self, "layer_types", tuple(
                FULL if (l + 1) % n == 0 else SLIDING
                for l in range(self.num_hidden_layers)))
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers as "
                f"{SLIDING!r} or {FULL!r}, got {self.layer_types}")

    @classmethod
    def from_dict(cls, d, **over):
        """From a published ``config.json`` dict (other keys ignored)."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        kw.update(over)
        for k in ("layer_types", "experts_held"):
            if kw.get(k) is not None:
                kw[k] = tuple(kw[k])
        return cls(**kw)

    # what serving.Engine reads of any model's configuration
    @property
    def max_seq_len(self):
        return self.max_position_embeddings

    @property
    def num_layers(self):
        return self.num_hidden_layers

    @property
    def served_model(self):
        return SERVED

    # what the shared expert layer (models/moe.py) reads, under its names
    @property
    def n_routed_experts(self):
        return self.num_experts

    @property
    def norm_topk_prob(self):
        return self.route_norm

    @property
    def routed_scaling_factor(self):
        return self.route_scale

    @property
    def route_norm_eps(self):
        return 1e-20

    @property
    def held(self):
        return self.experts_held or (0, self.num_experts)

    @property
    def num_moe_layers(self):
        return self.num_hidden_layers - self.num_dense_layers

    def kinds(self):
        """[(is an expert layer, attends to a window)] a layer."""
        return [(l >= self.num_dense_layers, t == SLIDING)
                for l, t in enumerate(self.layer_types)]

    def attention_layers(self, window):
        """How many layers attend to a window (or, False, to everything)."""
        return sum(1 for _, w in self.kinds() if w == window)


# ---------------------------------------------------------------------------
# parameters


def _layer_shapes(c, moe):
    H, d = c.hidden_size, c.head_dim
    nq, nkv = c.num_attention_heads * d, c.num_key_value_heads * d
    sh = {"attn_norm_g": (H,), "attn_post_norm_g": (H,), "ffn_norm_g": (H,),
          "ffn_post_norm_g": (H,), "wq": (H, nq), "wk": (H, nkv),
          "wv": (H, nkv), "wg": (H, nq), "wo": (nq, H), "q_norm_g": (d,),
          "k_norm_g": (d,)}
    if moe:
        E, F = c.num_experts, c.moe_intermediate_size
        Fs = F * c.num_shared_experts
        sh.update({"router_w": (H, E), "router_bias": (E,),
                   "experts_gate_w": (E, H, F), "experts_up_w": (E, H, F),
                   "experts_down_w": (E, F, H), "shared_gate_w": (H, Fs),
                   "shared_up_w": (H, Fs), "shared_down_w": (Fs, H)})
    else:
        F = c.intermediate_size
        sh.update({"gate_w": (H, F), "up_w": (H, F), "down_w": (F, H)})
    return sh


def _init_layers(c, key, moe, layers, dtype):
    out = {}
    for i, (name, shape) in enumerate(sorted(_layer_shapes(c, moe).items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_g"):
            a = jnp.ones((layers,) + shape, F32)
        elif name == "router_bias":
            a = 0.1 * jax.random.normal(k, (layers,) + shape, F32)
        else:
            a = c.initializer_range * jax.random.normal(
                k, (layers,) + shape, F32)
        out[name] = a.astype(dtype)
    return out


def init_afmoe_params(config, key, dtype=F32):
    """The functional tree: ``wte, head_w, normf_g``, ``dense`` and ``moe``
    (each kind's leaves stacked over its layers, in the layers' order)."""
    c = config
    H, V = c.hidden_size, c.vocab_size
    k = jax.random.split(key, 4)
    std = c.initializer_range
    return {"wte": (std * jax.random.normal(k[0], (V, H), F32)).astype(dtype),
            "head_w": (std * jax.random.normal(k[1], (H, V), F32)
                       ).astype(dtype),
            "normf_g": jnp.ones((H,), dtype),
            "dense": _init_layers(c, k[2], False, c.num_dense_layers, dtype),
            "moe": _init_layers(c, k[3], True, c.num_moe_layers, dtype)}


# ---------------------------------------------------------------------------
# pieces


def attention(p, h, config, pos, window, attend):
    """The attention sublayer on the stream h [B, T, H] (float32), before
    its residual add. ``window`` is the layer's kind; ``attend(q, k, v)`` ->
    (ctx [B, T, heads, d], carry) is where the plain and the paged forward
    differ."""
    c = config
    B, T, _ = h.shape
    d = c.head_dim
    x = rms_norm(h, p["attn_norm_g"], c.rms_norm_eps).astype(compute_of(c))
    q = mm(x, p["wq"]).reshape(B, T, c.num_attention_heads, d)
    k = mm(x, p["wk"]).reshape(B, T, c.num_key_value_heads, d)
    v = mm(x, p["wv"]).reshape(B, T, c.num_key_value_heads, d)
    q = rms_norm(q, p["q_norm_g"], c.rms_norm_eps)
    k = rms_norm(k, p["k_norm_g"], c.rms_norm_eps)
    if window:
        q, k = rotate(q, pos, c.rope_theta), rotate(k, pos, c.rope_theta)
    with jax.named_scope("pt_attn_window" if window else "pt_attn_full"):
        ctx, carry = attend(q, k, v)
    with jax.named_scope("pt_attn_gate"):
        ctx = ctx.reshape(B, T, -1) * jax.nn.sigmoid(mm(x, p["wg"]))
    out = mm(ctx, p["wo"], F32)
    return rms_norm(out, p["attn_post_norm_g"], c.rms_norm_eps), carry


def layer(p, h, config, pos, kind, attend, token_mask=None):
    """One layer of ``kind`` (is an expert layer, attends to a window) on
    the float32 stream h [B, T, H]. Returns h, attend's carry and the expert
    statistics (None for a dense layer)."""
    c = config
    moe, window = kind
    a, carry = attention(p, h, c, pos, window, attend)
    h = h + a
    if moe:
        y, stats = moe_ffn(p, h, c, token_mask)
    else:
        xn = rms_norm(h, p["ffn_norm_g"], c.rms_norm_eps)
        y, stats = ffn(xn.astype(compute_of(c)), p["gate_w"], p["up_w"],
                       p["down_w"]), None
    return h + rms_norm(y, p["ffn_post_norm_g"], c.rms_norm_eps), carry, stats


def _embed(params, config, ids):
    """The float32 stream: the token's embedding, scaled under muP."""
    x = params["wte"][ids].astype(F32)
    return x * math.sqrt(config.hidden_size) if config.mup_enabled else x


def forward(params, config, ids):
    """The plain causal forward over ids [B, T]: float32 logits [B, T, V]."""
    c = config
    B, T = ids.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))

    def layer_fn(h, p, kind, _mlp_index, _group_index):
        mask = window_mask(pos, pos, c.sliding_window if kind[1] else None)
        h, _, _ = layer(p, h, c, pos, kind, lambda q, k, v: (
            grouped_attend(q, k, v, mask, q.dtype), None))
        return h

    h = run_layers(params, c, _embed(params, c, ids), layer_fn)
    return final_logits(params, c, h)


# ---------------------------------------------------------------------------
# serving: the paged forward over the two groups' pools, and the engine's seam


def cache_groups(config):
    """The cache's groups, the full layers' first; a kind of layer that the
    configuration lacks has no group."""
    c = config
    row = (c.num_key_value_heads, c.head_dim)
    groups = []
    if c.attention_layers(False):
        groups.append(CacheGroup(("k_full", "v_full"),
                                 c.attention_layers(False), row))
    if c.attention_layers(True):
        groups.append(CacheGroup(("k_window", "v_window"),
                                 c.attention_layers(True), row,
                                 window=c.sliding_window))
    return tuple(groups)


def paged_forward(params, config, ids, pools, start, valid, table, page_size):
    """The fused chunk/decode forward the engine dispatches: ids [B, T] is
    each slot's window at positions start[b].. (valid[b] of them real).
    ``pools`` is each group's K and V ``[layers of the group, P, page_size,
    kv heads, lanes]`` and ``table`` its page table (a tuple with two
    groups), in ``cache_groups``' order; the pools are the layer scans'
    carry. A window layer writes and gathers its slot's ring, a full layer
    the context's pages. Returns logits [B, V] at each slot's last real
    position, the pools, and the expert statistics summed over the expert
    layers (the fullest expert's load as a maximum)."""
    c = config
    groups = cache_groups(c)
    tables = table if isinstance(table, tuple) else (table,)
    where = {g.window is not None: i for i, g in enumerate(groups)}
    B, T = ids.shape
    pos = start[:, None] + jnp.arange(T)[None, :]
    live = jnp.arange(T)[None, :] < valid[:, None]

    def layer_fn(carry, p, kind, _mlp_index, l):
        h, pools, stats = carry
        g = where[kind[1]]
        window = groups[g].window

        def attend(q, k, v):
            kc, vc = paged_kv_scatter(
                pools[2 * g], pools[2 * g + 1], l, k, v, tables[g], pos,
                valid, page_size, ring=window is not None)
            ctx = paged_attention_read(q, kc, vc, l, tables[g], pos,
                                       page_size, False, q.dtype,
                                       window=window)
            return ctx, pools[:2 * g] + (kc, vc) + pools[2 * g + 2:]

        h, pools, st = layer(p, h, c, pos, kind, attend, live)
        if st is not None:
            stats = jnp.concatenate([stats[:2] + st[:2],
                                     jnp.maximum(stats[2:], st[2:])])
        return h, pools, stats

    carry = (_embed(params, c, ids), tuple(pools), jnp.zeros((3,), jnp.int32))
    h, pools, stats = run_layers(params, c, carry, layer_fn)
    idx = jnp.maximum(valid - 1, 0)
    last = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]  # [B, H]
    return final_logits(params, c, last), pools, stats


class _Served(ServedModel):
    """What ``serving.Engine`` asks of this model (``serving/served_model.py``
    states the seam). What is not built for it yet is refused by name at
    construction: prefix sharing among them, for a ring page holds
    different positions over a request's life."""
    name = "afmoe"
    unsupported = frozenset({"spec", "quant", "adapters", "mp", "kv_transfer",
                             "prefix_cache"})

    def key(self, config):
        return config

    def view(self, key):
        return key

    def prepare(self, params, config):
        return jax.tree_util.tree_map(jnp.asarray, params)

    def geometry(self, config):
        return CacheGeometry(cache_groups(config),
                             config.compute_dtype or "float32")

    def kernel_ok(self, config, mp, page_size):
        logger.info(
            "paged decode kernel fallback to jnp gather (afmoe): %d KV heads "
            "under %d query heads, and a window layer's first live page: "
            "the kernel multiplies [nh, d] by [nh, d] tiles from page 0",
            config.num_key_value_heads, config.num_attention_heads)
        return False

    def forward(self, params, config, ids, pools, start, valid, table,
                page_size, **_gpt_options):
        return paged_forward(params, config, ids, pools, start, valid, table,
                             page_size)

    def record(self, stats, kind, config):
        metrics.observe_moe(kind, config.num_moe_layers, *stats)


SERVED = _Served()
