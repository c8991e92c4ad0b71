"""Expert-parallel Mixture-of-Experts (ref:
python/paddle/incubate/distributed/models/moe/moe_layer.py + gate/*).

TPU-first redesign. The reference routes tokens with dynamic-shape
scatter/gather plus NCCL global_scatter/global_gather; XLA needs static
shapes, so routing uses the GShard dense-dispatch formulation instead:

  * gate -> top-k expert choice with a STATIC per-expert capacity C;
  * dispatch/combine tensors [T, E, C] built with one-hots + cumsum;
  * token exchange via ONE `lax.all_to_all` over the 'ep' mesh axis each
    way (split experts / concat capacity) — the collective rides ICI;
  * expert FFNs run batched as [E_local, ep*C, D] einsums on the MXU.

Capacity overflow drops tokens (their combine weight is 0 and the residual
path carries them), matching GShard semantics rather than the reference's
unbounded dynamic buffers — that is the TPU-correct trade.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..... import nn
from .....nn.layer_base import Layer
from .....tensor_impl import as_tensor_data, wrap
from .....dispatch import apply as _apply


# ---------------------------------------------------------------------------
# gates (ref gate/{base,naive,switch,gshard}_gate.py)
class BaseGate(Layer):
    def __init__(self, num_expert, world_size=1):
        super().__init__()
        self.world_size = world_size
        self.num_expert = num_expert
        self.tot_expert = world_size * num_expert
        self.loss = None

    def forward(self, x):  # pragma: no cover - abstract
        raise NotImplementedError


class NaiveGate(BaseGate):
    """Linear gate + top-k (ref gate/naive_gate.py)."""

    def __init__(self, d_model, num_expert, world_size=1, topk=2):
        super().__init__(num_expert, world_size)
        self.gate = nn.Linear(d_model, self.tot_expert)
        self.top_k = topk

    def forward(self, inp, return_all_scores=False):
        from .....tensor import search as S
        gate = self.gate(inp)
        val, idx = S.topk(gate, k=self.top_k, axis=-1)
        if return_all_scores:
            return val, idx, gate
        return val, idx


class SwitchGate(NaiveGate):
    """top-1 switch routing with logit jitter in training
    (ref gate/switch_gate.py: switch_eps multiplicative noise,
    capacity=(train, eval) factors)."""

    def __init__(self, d_model, num_expert, world_size=1, topk=1,
                 switch_eps=0.1, capacity=(1.2, 2.4)):
        super().__init__(d_model, num_expert, world_size, topk=1)
        self.switch_eps = switch_eps
        self.capacity = capacity

    def capacity_factor(self):
        return self.capacity[0] if self.training else self.capacity[1]


class GShardGate(NaiveGate):
    """top-2 with capacity + random second-expert routing + aux
    load-balance loss (ref gate/gshard_gate.py)."""

    def __init__(self, d_model, num_expert, world_size=1, topk=2,
                 capacity=(1.2, 2.4), random_routing=True):
        super().__init__(d_model, num_expert, world_size, topk=2)
        self.capacity = capacity
        self.random_routing = random_routing

    def capacity_factor(self):
        return self.capacity[0] if self.training else self.capacity[1]


# ---------------------------------------------------------------------------
# dense dispatch construction (pure jax; static shapes)
def make_dispatch_and_combine(gates, top_k, capacity, normalize=True,
                              random_routing_key=None):
    """gates [T, E] (softmax probs) -> dispatch [T,E,C] (0/1),
    combine [T,E,C] (gate-weighted), aux load-balance loss (GShard eq.).

    With `random_routing_key`, non-first choices are kept with probability
    min(1, top_k * gate_prob) — GShard's random routing of the 2nd expert."""
    T, E = gates.shape
    C = capacity
    f32 = jnp.float32
    remaining = gates
    loc_base = jnp.zeros((E,), jnp.int32)
    chosen = []  # (onehot [T,E] int, pos [T], keep [T], gateval [T])
    for i in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)
        gval = jnp.take_along_axis(gates, idx[:, None], 1)[:, 0]
        if i > 0 and random_routing_key is not None:
            u = jax.random.uniform(
                jax.random.fold_in(random_routing_key, i), (T,), f32)
            onehot = onehot * (u < top_k * gval).astype(jnp.int32)[:, None]
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot +
                       loc_base[None]) * onehot, axis=1)
        keep = (pos < C) & (onehot.sum(-1) > 0)
        chosen.append((onehot, pos, keep, gval))
        loc_base = loc_base + jnp.sum(onehot * keep[:, None].astype(jnp.int32),
                                      axis=0)
        remaining = remaining * (1 - onehot.astype(gates.dtype))

    denom = sum(jnp.where(k, g, 0.0) for _, _, k, g in chosen) if normalize \
        else 1.0
    denom = jnp.maximum(denom, 1e-9) if normalize else 1.0
    dispatch = jnp.zeros((T, E, C), bool)
    combine = jnp.zeros((T, E, C), f32)
    for onehot, pos, keep, gval in chosen:
        oh_pos = jax.nn.one_hot(jnp.where(keep, pos, 0), C, dtype=f32)
        d = (onehot.astype(f32) * keep[:, None].astype(f32))[..., None] * \
            oh_pos[:, None, :]
        dispatch = dispatch | d.astype(bool)
        w = gval / denom if normalize else gval
        combine = combine + d * w[:, None, None]

    # aux loss (GShard): E * mean_e(fraction_of_tokens_e * mean_gate_e),
    # computed on the FIRST choice like the paper
    first = chosen[0][0].astype(f32)
    aux = E * jnp.mean(jnp.mean(first, axis=0) * jnp.mean(gates, axis=0)) \
        * top_k
    return dispatch, combine, aux


def expert_parallel_moe(x, gate_w, gate_b, w1, b1, w2, b2, *, mesh=None,
                        axis="ep", top_k=2, capacity_factor=1.25,
                        act="gelu", normalize=True, switch_jitter=0.0,
                        routing_key=None, random_routing=False):
    """Functional EP-MoE FFN. x [T, D] (token-sharded over `axis` under the
    mesh); expert weights w1 [E, D, H], w2 [E, H, D] (expert-sharded over
    `axis`). Returns (y [T, D], aux_loss scalar).

    switch_jitter: multiplicative logit noise in [1-eps, 1+eps] (SwitchGate
    training); random_routing: keep non-first experts with prob
    min(1, k*gate) (GShardGate). Both need `routing_key`."""
    act_fn = {"relu": jax.nn.relu, "gelu": jax.nn.gelu}[act]
    E = w1.shape[0]
    ep = mesh.shape.get(axis, 1) if mesh is not None else 1
    if ep > 1:
        assert E % ep == 0, (
            f"num_experts {E} must divide by ep degree {ep} for all_to_all")
        assert x.shape[0] % ep == 0, (
            f"token count {x.shape[0]} must divide by ep degree {ep}")
    T_local = x.shape[0] // max(ep, 1)
    C = max(1, math.ceil(top_k * T_local * capacity_factor / E))

    def local_fn(xs, gw, gb, w1s, b1s, w2s, b2s):
        xs = xs.reshape(xs.shape[-2:]) if xs.ndim == 3 else xs
        logits = (xs @ gw + gb).astype(jnp.float32)
        if switch_jitter and routing_key is not None:
            noise = jax.random.uniform(
                jax.random.fold_in(routing_key, 17), logits.shape,
                jnp.float32, 1.0 - switch_jitter, 1.0 + switch_jitter)
            logits = logits * noise
        gates = jax.nn.softmax(logits, axis=-1)
        dispatch, combine, aux = make_dispatch_and_combine(
            gates, top_k, C, normalize,
            random_routing_key=(routing_key if random_routing else None))
        sent = jnp.einsum("tec,td->ecd", dispatch.astype(xs.dtype), xs)
        if ep > 1:
            # [E, C, D] -> peers get their experts -> [E/ep, ep*C, D]
            recv = lax.all_to_all(sent, axis, split_axis=0, concat_axis=1,
                                  tiled=True)
            aux = lax.pmean(aux, axis)
        else:
            recv = sent
        h = act_fn(jnp.einsum("ecd,edh->ech", recv, w1s) + b1s[:, None])
        out = jnp.einsum("ech,ehd->ecd", h, w2s) + b2s[:, None]
        if ep > 1:
            back = lax.all_to_all(out, axis, split_axis=1, concat_axis=0,
                                  tiled=True)
        else:
            back = out
        y = jnp.einsum("tec,ecd->td", combine.astype(xs.dtype),
                       back.astype(xs.dtype))
        return y, aux

    if mesh is None or ep == 1:
        return local_fn(x, gate_w, gate_b, w1, b1, w2, b2)

    tok = P(axis, None)
    exp = P(axis, *([None] * (w1.ndim - 1)))
    mapped = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(tok, P(), P(), exp, P(axis, None), exp, P(axis, None)),
        out_specs=(tok, P()),
        axis_names=frozenset({axis}))
    return mapped(x, gate_w, gate_b, w1, b1, w2, b2)


class MoELayer(Layer):
    """Expert-parallel MoE FFN layer (ref moe_layer.py MoELayer API shape;
    experts stored STACKED [E, ...] for batched MXU einsums instead of the
    reference's per-expert Layer list).

    `gate` may be a string ("gshard" | "switch" | "naive") or a gate
    instance (GShardGate/SwitchGate/NaiveGate); with an instance, its
    linear drives routing, its top_k/capacity/noise settings apply, and
    its `.loss` is set to the aux load-balance term after each forward
    (also mirrored on `self.l_aux`)."""

    def __init__(self, d_model, d_hidden, num_experts, top_k=2,
                 capacity_factor=1.25, gate="gshard", act="gelu",
                 mesh=None, ep_axis="ep", seed=0):
        super().__init__()
        self.d_model = d_model
        self.num_experts = num_experts
        self.act = act
        self.mesh = mesh
        self.ep_axis = ep_axis
        self._gate_owns_capacity = isinstance(gate, BaseGate)
        if isinstance(gate, BaseGate):
            self.gate = gate
        elif gate == "switch":
            self.gate = SwitchGate(d_model, num_experts)
        elif gate == "naive":
            self.gate = NaiveGate(d_model, num_experts, topk=top_k)
        else:
            self.gate = GShardGate(d_model, num_experts, topk=top_k)
        self._default_capacity_factor = capacity_factor
        init = nn.initializer.Normal(0.0, (2.0 / d_model) ** 0.5)
        init2 = nn.initializer.Normal(0.0, (2.0 / d_hidden) ** 0.5)
        self.w1 = self.create_parameter(
            [num_experts, d_model, d_hidden],
            attr=nn.ParamAttr(initializer=init))
        self.b1 = self.create_parameter([num_experts, d_hidden], is_bias=True)
        self.w2 = self.create_parameter(
            [num_experts, d_hidden, d_model],
            attr=nn.ParamAttr(initializer=init2))
        self.b2 = self.create_parameter([num_experts, d_model], is_bias=True)
        self.l_aux = None

    def forward(self, x):
        shape = x.shape
        flat = as_tensor_data(x).reshape(-1, self.d_model)
        g = self.gate
        cf = (g.capacity_factor()
              if self._gate_owns_capacity and hasattr(g, "capacity_factor")
              else self._default_capacity_factor)
        jitter = getattr(g, "switch_eps", 0.0) if g.training else 0.0
        rand2 = getattr(g, "random_routing", False) and g.training
        key = None
        if jitter or rand2:
            from .....framework.random import next_key
            key = next_key()

        def f(xs, gw, gb, w1, b1, w2, b2):
            y, aux = expert_parallel_moe(
                xs, gw, gb, w1, b1, w2, b2, mesh=self.mesh,
                axis=self.ep_axis, top_k=g.top_k, capacity_factor=cf,
                act=self.act, switch_jitter=jitter, routing_key=key,
                random_routing=rand2)
            return y, aux

        y, aux = _apply(f, wrap(flat), g.gate.weight, g.gate.bias,
                        self.w1, self.b1, self.w2, self.b2,
                        op_name="moe")
        self.l_aux = aux
        g.loss = aux
        from .....tensor import manipulation as M
        return M.reshape(y, list(shape))
