"""What the expert models share (``models/xing4.py``, ``models/afmoe.py``,
``models/lfm2.py``): the RMS norm, the gated FFN, the final norm and head,
the rotation of a head's pairs, the sigmoid-routed expert layer beside a
shared expert (or none), and the walk over a model's layers as one scan a
segment of repeated kinds. One function each, so that a change to the expert
layer is judged on every expert model the benchmark runs.

The expert layer reads of a configuration object: ``n_routed_experts``,
``num_experts_per_tok``, ``norm_topk_prob``, ``route_norm_eps`` (what the
normalisation adds to the chosen scores' sum, as the model's published code
has it), ``routed_scaling_factor``, ``held`` (the range of routed experts
this chip holds), ``rms_norm_eps`` and ``compute_dtype``; of a layer's
leaves: ``ffn_norm_g``, ``router_w``, ``router_bias``,
``experts_{gate,up,down}_w`` and, where the layer has a shared expert,
``shared_{gate,up,down}_w``.
A model whose published keys differ states them as properties."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..ops.pallas_kernels.grouped_matmul import grouped_ffn

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def rms_norm(x, g, eps):
    """RMS norm in float32, back in x's type; ``g`` None is no gain."""
    xf = x.astype(F32)
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    if g is not None:
        xf = xf * g.astype(F32)
    return xf.astype(x.dtype)


def mm(x, w, out=None):
    """x @ w in x's type; ``out`` float32 keeps the product's float32 sums
    (what a sublayer hands back to the float32 stream)."""
    return jnp.matmul(x, w.astype(x.dtype), preferred_element_type=out)


def ffn(x, gate_w, up_w, down_w):
    return mm(jax.nn.silu(mm(x, gate_w)) * mm(x, up_w), down_w, F32)


def final_logits(params, config, h):
    """The final norm (float32) and the untied head over h [..., H]."""
    with jax.named_scope("pt_head"):
        hn = rms_norm(h.astype(F32), params["normf_g"], config.rms_norm_eps)
        return hn @ params["head_w"].astype(F32)


def compute_of(config):
    return jnp.dtype(config.compute_dtype or "float32")


def rotate(x, pos, theta):
    """x [B, T, heads, d] rotated at integer positions pos [B, T]: the pair
    (i, i + d/2) turns by position x theta^(-2i/d)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[..., None, None] * inv               # [B, T, 1, d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(F32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def moe_route(xn32, router_w, router_bias, config):
    """Routing of tokens xn32 [N, H] (float32, as the published code routes):
    chosen experts [N, k] and their weights [N, k]."""
    c = config
    s = jax.nn.sigmoid(jnp.matmul(xn32, router_w.astype(F32),
                                  precision=HIGHEST))
    _, idx = jax.lax.top_k(s + router_bias.astype(F32), c.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if c.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + c.route_norm_eps)
    return idx, w * c.routed_scaling_factor


EXPERTS = ("experts_gate_w", "experts_up_w", "experts_down_w")


def layer_leaves(stack, i):
    """Layer ``i``'s leaves out of its kind's stack, but the expert stacks:
    those stay whole, with ``expert_layer`` i beside them, for the grouped
    product reads a layer's experts off the stack as stored (a slice here
    would be a copy of the layer's experts on a TPU)."""
    leaves = {k: v if k in EXPERTS else v[i] for k, v in stack.items()}
    if EXPERTS[0] in stack:
        leaves["expert_layer"] = i
    return leaves


def _ragged_ffn(x, gate, up, down, group_sizes, layer, lo):
    """What ``grouped_matmul.grouped_ffn`` computes, off a TPU: the layer's
    held experts sliced out and ``jax.lax.ragged_dot`` over them."""
    def held(stack):
        return jax.lax.dynamic_index_in_dim(stack, layer, 0, False)[
            lo:lo + group_sizes.shape[0]].astype(x.dtype)

    a = jax.lax.ragged_dot(x, held(gate), group_sizes).astype(F32)
    b = jax.lax.ragged_dot(x, held(up), group_sizes).astype(F32)
    act = (a * jax.nn.sigmoid(a) * b).astype(x.dtype)
    return jax.lax.ragged_dot(act, held(down), group_sizes,
                              preferred_element_type=F32)


def moe_ffn(p, x, config, token_mask=None, held=None, shared=True):
    """The expert layer's FFN on the sublayer input x [B, T, H] (float32,
    not yet normed). Routes over every expert, computes the part of the result that
    the ``held`` range of routed experts gives (default: the
    configuration's), plus the shared expert where ``shared``. Returns the
    result and int32 ``[assignments to held experts, held experts that got
    a token, the fullest held expert's tokens]`` over the tokens that
    ``token_mask`` [B, T] keeps.

    The routed part multiplies each token by the experts it chose: its
    (token, expert) pairs of held experts and kept tokens, sorted by expert
    (a counting sort: every ``sort`` of a step lies under the sampling
    tail's cond), one grouped product a matrix over the experts that hold
    rows, each pair's row weighed in float32 and summed back into its token.
    The expert stacks are one layer's ``[E, ...]``, or ``[L, E, ...]`` read at
    ``p["expert_layer"]`` (``layer_leaves``)."""
    c = config
    B, T, H = x.shape
    N, k = B * T, c.num_experts_per_tok
    lo, hi = held or c.held
    compute = compute_of(c)
    xn32 = rms_norm(x.astype(F32), p["ffn_norm_g"], c.rms_norm_eps)
    xn = xn32.astype(compute).reshape(N, H)
    with jax.named_scope("pt_moe_route"):
        idx, w = moe_route(xn32.reshape(N, H), p["router_w"],
                           p["router_bias"], c)
        hot = jax.nn.one_hot(idx, c.n_routed_experts, dtype=F32)  # [N, k, E]
        load = jnp.sum(hot, axis=1)[:, lo:hi]                     # [N, E']
        if token_mask is not None:
            load = load * token_mask.reshape(N, 1)
        per_expert = jnp.sum(load, axis=0)
        stats = jnp.stack([jnp.sum(per_expert), jnp.sum(per_expert > 0),
                           jnp.max(per_expert)]).astype(jnp.int32)
    with jax.named_scope("pt_moe_experts"):
        e = idx.reshape(N * k) - lo
        keep = (e >= 0) & (e < hi - lo)
        if token_mask is not None:
            keep = keep & jnp.repeat(token_mask.reshape(N), k)
        key = jnp.where(keep, e, hi - lo)              # dropped pairs last
        count = jnp.cumsum(key[:, None] == jnp.arange(hi - lo + 1), axis=0,
                           dtype=jnp.int32)            # [N k, E' + 1]
        start = jnp.cumsum(count[-1], dtype=jnp.int32) - count[-1]
        dest = start[key] + jnp.take_along_axis(count, key[:, None], 1)[:, 0]
        dest = dest - 1                                # a pair's sorted row
        src = jnp.zeros_like(dest).at[dest].set(
            jnp.arange(N * k, dtype=dest.dtype))       # a sorted row's pair
        stacks = [p[name] for name in EXPERTS]
        layer = p.get("expert_layer", 0)
        if stacks[0].ndim == 3:
            stacks = [s[None] for s in stacks]
        rows = jax.lax.platform_dependent(
            xn[src // k], *stacks, count[-1, :-1], layer,
            tpu=functools.partial(grouped_ffn, lo=lo),
            default=functools.partial(_ragged_ffn, lo=lo))
        y = jnp.where(keep[:, None], rows[dest] * w.reshape(N * k, 1), 0.0)
        y = jnp.sum(y.reshape(N, k, H), axis=1)
        if shared:
            y = y + ffn(xn, p["shared_gate_w"], p["shared_up_w"],
                        p["shared_down_w"])
    return y.reshape(B, T, H), stats


def layer_plan(kinds):
    """[(pattern, repeats)] covering the layers' ``kinds`` in order: at each
    point the pattern (a run of one kind, or a period of several kinds seen
    at least twice) that covers the most layers."""
    plan, i = [], 0
    while i < len(kinds):
        best = (1, 1)
        for period in range(1, (len(kinds) - i) // 2 + 1):
            pattern, n = kinds[i:i + period], 1
            while kinds[i + n * period:i + (n + 1) * period] == pattern:
                n += 1
            if n > 1 and period * n > best[0] * best[1]:
                best = (period, n)
        plan.append((tuple(kinds[i:i + best[0]]), best[1]))
        i += best[0] * best[1]
    return plan


def run_layers(params, config, carry, layer_fn):
    """``layer_fn(carry, leaves, kind, moe_index, group_index) -> carry``
    over every layer in order: one scan a segment of ``layer_plan``, the
    layer's leaves indexed out of its kind's stack by the repeat.
    A kind is (is an expert layer, which of the model's two operators: a
    window against a full attention, a convolution against an attention).
    ``moe_index`` counts the layers of the same MLP kind before it (its
    place in ``params["dense"]`` or ``params["moe"]``), ``group_index``
    those of the same operator (its place in its cache group)."""
    seen = {}                       # key -> layers met so far

    for pattern, repeats in layer_plan(config.kinds()):
        base = dict(seen)
        per = {}
        for moe, window in pattern:
            for key in (("mlp", moe), ("attn", window)):
                per[key] = per.get(key, 0) + 1

        def body(carry, rep, pattern=pattern, base=base, per=per):
            at = {}
            for kind in pattern:
                moe, window = kind
                idx = []
                for key in (("mlp", moe), ("attn", window)):
                    idx.append(base.get(key, 0) + rep * per[key]
                               + at.get(key, 0))
                    at[key] = at.get(key, 0) + 1
                leaves = layer_leaves(params["moe" if moe else "dense"],
                                      idx[0])
                carry = layer_fn(carry, leaves, kind, idx[0], idx[1])
            return carry, None

        # what the walk itself costs on a device trace (a layer's leaves
        # indexed out of their stacks, the loop's carry) is pt_layers'; a
        # stage inside a layer keeps its own, innermost, scope
        with jax.named_scope("pt_layers"):
            carry, _ = jax.lax.scan(body, carry,
                                    jnp.arange(repeats, dtype=jnp.int32))
        for key, n in per.items():
            seen[key] = seen.get(key, 0) + n * repeats
    return carry
