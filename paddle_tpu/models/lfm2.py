"""LFM2-MoE (``model_type`` ``lfm2_moe``: Liquid AI's LFM2 family): a decoder
most of whose layers mix tokens by a gated SHORT CONVOLUTION (a depthwise
causal convolution over ``conv_L_cache`` positions, gated on both sides) and
keep no keys or values at all, with a ``full_attention`` layer of grouped KV
heads among every few (``layer_types``); a gated FFN in the leading
``num_dense_layers``, sigmoid-routed experts with no shared expert in the
rest, under the keys of its published ``config.json``.

What is here: the configuration, seeded parameters, the plain forward
(``forward``: no cache), and the paged forward that ``serving.Engine``
dispatches (``paged_forward``) with the seam object the engine finds through
``Lfm2Config.served_model``. The cache has two groups of layers: the
attention layers' K and V pools, paged, and the convolution layers' STATE, a
slot's last ``conv_L_cache - 1`` rows of ``u`` a layer whatever its context
(``serving/served_model.py``: a state group).

Layer equations (the plain reference, ``benchmark/families/lfm2/
reference.py``, states the same independently; tests hold the two together):

* ``h = E[ids]``; every layer ``h = h + op(rms(h; operator_norm))``, ``h = h
  + mlp(rms(h; ffn_norm))``; after the last layer ``rms(h; embedding_norm)``
  and the head, tied to ``E``;
* a ``conv`` operator on ``x [T, H]``: ``[B | C | X] = x W_in`` (``W_in: H ->
  3H``, no bias), ``u = B * X``, ``y_t = sum_{j=0..L-1} w[:, j] * u_{t-(L-1)
  +j}`` a channel (depthwise, causal, ``L = conv_L_cache``, ``u`` zero before
  the sequence), ``out = (C * y) W_out``. No activation. What a slot keeps of
  a conv layer is the last ``L - 1`` rows of ``u``;
* a ``full_attention`` operator: ``q = x Wq`` as ``num_attention_heads``
  heads, ``k = x Wk`` and ``v = x Wv`` as ``num_key_value_heads`` heads, no
  bias; q and k take an RMS norm over the head (one gain each, shared by the
  heads), then both are rotated (``rope_theta``, the whole head, pairs ``(i,
  i + head_dim / 2)``); query head j reads KV head ``j // (heads / kv
  heads)``; causal softmax at scale ``head_dim^-0.5``; then ``Wo``;
* the leading ``num_dense_layers`` have a gated FFN of ``intermediate_size``,
  the others the expert layer of ``models/moe.py``: ``num_experts`` experts
  of ``moe_intermediate_size``, the ``num_experts_per_tok`` largest of
  ``sigmoid(x Wr) + expert_bias``, weights the chosen scores over (their sum
  + 1e-6) (``norm_topk_prob``) times ``routed_scaling_factor``, dropless, no
  shared expert.

The tree holds a layer's MLP leaves (and its two norms) stacked by the kind
of MLP (``dense``, ``moe``) and its operator's leaves stacked by the kind of
operator (``conv``, ``attn``), each in the layers' order; ``layer_plan``
turns the sequence of kinds into a few segments, each ONE scan over its
repeats (the published 40 layers: two dense conv layers, nine periods of
attention, conv, conv, conv, then attention, conv).
"""
from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp

from ..serving import metrics
from ..serving.paged_attention import grouped_attend, pad_lanes, \
    paged_attention_read, paged_kv_scatter, window_mask
from ..serving.served_model import CacheGeometry, CacheGroup, ServedModel
from .moe import F32, compute_of, ffn, final_logits, mm, moe_ffn, rms_norm, \
    rotate, run_layers

logger = logging.getLogger("paddle_tpu.lfm2")

CONV, FULL = "conv", "full_attention"
# the published pattern: a full_attention layer at 2, 6, ..., 38
PUBLISHED_LAYER_TYPES = tuple(FULL if l % 4 == 2 else CONV for l in range(40))


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """The published keys (defaults: LFM2-24B-A2B), hashable so that it keys
    the engine's memoized builders; ``layer_types`` a tuple, None is the
    published 40. ``rope_theta`` is ``rope_parameters.rope_theta``. Not
    published: ``head_dim`` (None is hidden / heads), ``experts_held`` (the
    range of routed experts this chip holds; None is all),
    ``initializer_range``, ``compute_dtype``."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = None
    layer_types: tuple = None
    conv_L_cache: int = 3
    conv_bias: bool = False
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    norm_eps: float = 1e-5
    initializer_range: float = 0.02
    compute_dtype: str = "float32"
    experts_held: tuple = None

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.hidden_size // self.num_attention_heads)
        if self.layer_types is None:
            object.__setattr__(self, "layer_types", PUBLISHED_LAYER_TYPES)
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {CONV, FULL}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers as "
                f"{CONV!r} or {FULL!r}, got {self.layer_types}")
        if self.conv_bias:
            raise ValueError("conv_bias is not built (the published "
                             "configurations have none)")

    @classmethod
    def from_dict(cls, d, **over):
        """From a published ``config.json`` dict (other keys ignored)."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        if "rope_parameters" in d:
            kw["rope_theta"] = float(d["rope_parameters"]["rope_theta"])
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
    def rms_norm_eps(self):
        return self.norm_eps

    @property
    def route_norm_eps(self):
        return 1e-6

    @property
    def held(self):
        return self.experts_held or (0, self.num_experts)

    @property
    def num_moe_layers(self):
        return self.num_hidden_layers - self.num_dense_layers

    def kinds(self):
        """[(is an expert layer, its operator is a convolution)] a layer."""
        return [(l >= self.num_dense_layers, t == CONV)
                for l, t in enumerate(self.layer_types)]

    def operator_layers(self, conv):
        """How many layers mix by a convolution (or, False, by attention)."""
        return sum(1 for _, c in self.kinds() if c == conv)


# ---------------------------------------------------------------------------
# parameters


def _stack_shapes(c):
    """{stack: {leaf: one layer's shape}} of the four stacks."""
    H, d, L = c.hidden_size, c.head_dim, c.conv_L_cache
    nq, nkv = c.num_attention_heads * d, c.num_key_value_heads * d
    E, F = c.num_experts, c.moe_intermediate_size
    norms = {"operator_norm_g": (H,), "ffn_norm_g": (H,)}
    return {
        "dense": {**norms, "gate_w": (H, c.intermediate_size),
                  "up_w": (H, c.intermediate_size),
                  "down_w": (c.intermediate_size, H)},
        "moe": {**norms, "router_w": (H, E), "router_bias": (E,),
                "experts_gate_w": (E, H, F), "experts_up_w": (E, H, F),
                "experts_down_w": (E, F, H)},
        "conv": {"in_w": (H, 3 * H), "taps": (H, L), "out_w": (H, H)},
        "attn": {"wq": (H, nq), "wk": (H, nkv), "wv": (H, nkv),
                 "wo": (nq, H), "q_norm_g": (d,), "k_norm_g": (d,)}}


def init_lfm2_params(config, key, dtype=F32):
    """The functional tree: ``wte`` (the head is tied to it), ``normf_g``
    (the published ``embedding_norm``), and the stacks ``dense``, ``moe``
    (MLP leaves and the layer's two norms), ``conv``, ``attn`` (operator
    leaves), each over its layers in the layers' order."""
    c = config
    layers = {"dense": c.num_dense_layers, "moe": c.num_moe_layers,
              "conv": c.operator_layers(True),
              "attn": c.operator_layers(False)}
    keys = jax.random.split(key, 1 + len(layers))
    std = c.initializer_range
    tree = {"wte": (std * jax.random.normal(
        keys[0], (c.vocab_size, c.hidden_size), F32)).astype(dtype),
        "normf_g": jnp.ones((c.hidden_size,), dtype)}
    for k, (stack, shapes) in zip(keys[1:], sorted(_stack_shapes(c).items())):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            shape = (layers[stack],) + shape
            if name.endswith("_g"):
                a = jnp.ones(shape, F32)
            elif name == "router_bias":
                a = (0.1 if c.use_expert_bias else 0.0) * jax.random.normal(
                    jax.random.fold_in(k, i), shape, F32)
            else:
                a = std * jax.random.normal(jax.random.fold_in(k, i), shape,
                                            F32)
            out[name] = a.astype(dtype)
        tree[stack] = out
    return tree


# ---------------------------------------------------------------------------
# pieces


def short_conv(u, before, taps):
    """The depthwise causal convolution of u [B, T, H] whose ``L - 1`` rows
    before the window are ``before`` [B, L - 1, H], by taps [H, L]: ``y_t =
    sum_j taps[:, j] * u_{t-(L-1)+j}``, float32 sums, back in u's type.
    Returns y and the rows it ran over, [B, L - 1 + T, H]."""
    T, L = u.shape[1], taps.shape[-1]
    rows = jnp.concatenate([before.astype(u.dtype), u], axis=1)
    w = taps.astype(F32)
    y = sum(rows[:, j:j + T].astype(F32) * w[:, j] for j in range(L))
    return y.astype(u.dtype), rows


def conv_operator(p, x, mix):
    """The gated short convolution on the normed input x [B, T, H] (compute
    type), before its residual add. ``mix(u)`` -> (y [B, T, H], carry) is
    where the plain and the paged forward differ: what lies before the
    window, and where its last rows go."""
    with jax.named_scope("pt_conv_in"):
        b, c, xx = jnp.split(mm(x, p["in_w"]), 3, axis=-1)
        u = b * xx
    with jax.named_scope("pt_conv_mix"):
        y, carry = mix(u)
    with jax.named_scope("pt_conv_out"):
        out = mm(c * y, p["out_w"], F32)
    return out, carry


def attention_operator(p, x, config, pos, attend):
    """The attention operator on the normed input x [B, T, H] (compute
    type), before its residual add; ``attend(q, k, v)`` -> (ctx [B, T,
    heads, d], carry) is where the plain and the paged forward differ."""
    c = config
    B, T, _ = x.shape
    d = c.head_dim
    q = mm(x, p["wq"]).reshape(B, T, c.num_attention_heads, d)
    k = mm(x, p["wk"]).reshape(B, T, c.num_key_value_heads, d)
    v = mm(x, p["wv"]).reshape(B, T, c.num_key_value_heads, d)
    q = rotate(rms_norm(q, p["q_norm_g"], c.norm_eps), pos, c.rope_theta)
    k = rotate(rms_norm(k, p["k_norm_g"], c.norm_eps), pos, c.rope_theta)
    with jax.named_scope("pt_attn_gqa"):
        ctx, carry = attend(q, k, v)
    return mm(ctx.reshape(B, T, -1), p["wo"], F32), carry


def layer(p, op, h, config, pos, kind, operate, token_mask=None):
    """One layer of ``kind`` (is an expert layer, mixes by a convolution) on
    the float32 stream h [B, T, H]; ``p`` its MLP leaves and norms, ``op``
    its operator's leaves, ``operate`` the operator's ``mix`` or ``attend``.
    Returns h, operate's carry and the expert statistics (None for a dense
    layer)."""
    c = config
    moe, conv = kind
    x = rms_norm(h, p["operator_norm_g"], c.norm_eps).astype(compute_of(c))
    out, carry = conv_operator(op, x, operate) if conv else \
        attention_operator(op, x, c, pos, operate)
    h = h + out
    if moe:
        y, stats = moe_ffn(p, h, c, token_mask, shared=False)
    else:
        xn = rms_norm(h, p["ffn_norm_g"], c.norm_eps)
        y, stats = ffn(xn.astype(compute_of(c)), p["gate_w"], p["up_w"],
                       p["down_w"]), None
    return h + y, carry, stats


def _operator_leaves(params, kind, index):
    stack = params["conv" if kind[1] else "attn"]
    return jax.tree_util.tree_map(lambda a: a[index], stack)


def _logits(params, config, h):
    """The final norm and the head tied to the embedding."""
    return final_logits({"normf_g": params["normf_g"],
                         "head_w": params["wte"].T}, config, h)


def forward(params, config, ids):
    """The plain causal forward over ids [B, T]: float32 logits [B, T, V]."""
    c = config
    B, T = ids.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    mask = window_mask(pos, pos)

    def layer_fn(h, p, kind, _mlp_index, index):
        op = _operator_leaves(params, kind, index)
        if kind[1]:
            def operate(u):
                zeros = jnp.zeros((B, c.conv_L_cache - 1, u.shape[-1]),
                                  u.dtype)
                return short_conv(u, zeros, op["taps"])[0], None
        else:
            def operate(q, k, v):
                return grouped_attend(q, k, v, mask, q.dtype), None
        return layer(p, op, h, c, pos, kind, operate)[0]

    h = run_layers(params, c, params["wte"][ids].astype(F32), layer_fn)
    return _logits(params, c, h)


# ---------------------------------------------------------------------------
# serving: the paged forward over pages and state, and the engine's seam


def cache_groups(config):
    """The cache's groups: the attention layers' K and V, paged, then the
    convolution layers' state, a slot's last ``L - 1`` rows of ``u``; a kind
    of layer that the configuration lacks has no group."""
    c = config
    groups = []
    if c.operator_layers(False):
        groups.append(CacheGroup(("k", "v"), c.operator_layers(False),
                                 (c.num_key_value_heads, c.head_dim)))
    if c.operator_layers(True):
        groups.append(CacheGroup(("u",), c.operator_layers(True),
                                 (c.conv_L_cache - 1, c.hidden_size),
                                 paged=False))
    return tuple(groups)


def paged_forward(params, config, ids, pools, start, valid, table, page_size):
    """The fused chunk/decode forward the engine dispatches: ids [B, T] is
    each slot's window at positions start[b].. (valid[b] of them real).
    ``pools`` is K and V ``[attention layers, P, page_size, kv heads,
    lanes]`` and the state ``[conv layers, slots, L - 1, lanes]``; ``table``
    the page table [B, pages] and the slots' numbers [B], in
    ``cache_groups``' order; pools and state are the layer scans' carry. An
    attention layer writes and gathers its context's pages. A conv layer
    reads its slots' rows of the state (zero where ``start`` is 0: a new
    occupant), convolves the window after them and writes back the last
    ``L - 1`` REAL rows (rows ``valid..valid + L - 2`` of state + window);
    a slot with ``valid`` 0 keeps what it had. Returns logits [B, V] at each
    slot's last real position, the pools, and the expert statistics summed
    over the expert layers (the fullest expert's load as a maximum)."""
    c = config
    groups = cache_groups(c)
    tables = table if isinstance(table, tuple) else (table,)
    where, at = {}, 0
    for i, g in enumerate(groups):
        where[not g.paged] = (i, at)       # operator kind -> (group, pool)
        at += len(g.names)
    B, T = ids.shape
    H, keep = c.hidden_size, c.conv_L_cache - 1
    pos = start[:, None] + jnp.arange(T)[None, :]
    live = jnp.arange(T)[None, :] < valid[:, None]
    fresh, moved = (start == 0)[:, None, None], (valid > 0)[:, None, None]
    last_rows = (valid[:, None] + jnp.arange(keep)[None, :])[:, :, None]

    def layer_fn(carry, p, kind, _mlp_index, l):
        h, pools, stats = carry
        g, at = where[kind[1]]
        op = _operator_leaves(params, kind, l)

        def mix(u):
            state, slot = pools[at], tables[g]
            old = state[l, slot]                         # [B, L - 1, lanes]
            y, rows = short_conv(u, jnp.where(fresh, 0, old[..., :H]),
                                 op["taps"])
            new = jnp.take_along_axis(rows, last_rows, axis=1)
            new = jnp.where(moved, pad_lanes(new.astype(state.dtype), state),
                            old)
            return y, pools[:at] + (state.at[l, slot].set(new),) \
                + pools[at + 1:]

        def attend(q, k, v):
            kc, vc = paged_kv_scatter(pools[at], pools[at + 1], l, k, v,
                                      tables[g], pos, valid, page_size)
            ctx = paged_attention_read(q, kc, vc, l, tables[g], pos,
                                       page_size, False, q.dtype)
            return ctx, pools[:at] + (kc, vc) + pools[at + 2:]

        h, pools, st = layer(p, op, h, c, pos, kind,
                             mix if kind[1] else attend, live)
        if st is not None:
            stats = jnp.concatenate([stats[:2] + st[:2],
                                     jnp.maximum(stats[2:], st[2:])])
        return h, pools, stats

    carry = (params["wte"][ids].astype(F32), tuple(pools),
             jnp.zeros((3,), jnp.int32))
    h, pools, stats = run_layers(params, c, carry, layer_fn)
    idx = jnp.maximum(valid - 1, 0)
    last = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]  # [B, H]
    return _logits(params, c, last), pools, stats


class _Served(ServedModel):
    """What ``serving.Engine`` asks of this model (``serving/served_model.py``
    states the seam). What is not built for it yet is refused by name at
    construction: prefix sharing among them, for a shared page does not
    bring the conv layers' state at its end."""
    name = "lfm2"
    unsupported = frozenset({"prefix_cache", "spec", "quant", "adapters",
                             "mp", "kv_transfer"})

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
            "paged decode kernel fallback to jnp gather (lfm2): %d KV heads "
            "under %d query heads: the kernel multiplies [nh, d] by [nh, d] "
            "tiles", config.num_key_value_heads, config.num_attention_heads)
        return False

    def forward(self, params, config, ids, pools, start, valid, table,
                page_size, **_gpt_options):
        return paged_forward(params, config, ids, pools, start, valid, table,
                             page_size)

    def record(self, stats, kind, config):
        metrics.observe_moe(kind, config.num_moe_layers, *stats)


SERVED = _Served()
