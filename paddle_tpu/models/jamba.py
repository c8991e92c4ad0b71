"""Jamba (``model_type`` ``jamba``: AI21's hybrid of Mamba-1 selective-scan
layers and attention layers) with one dense MLP a layer (``num_experts`` 1),
under the keys of its published ``config.json``.

What is here: the configuration, seeded parameters, the plain forward
(``forward``: no cache), and the paged forward that ``serving.Engine``
dispatches (``paged_forward``) with the seam object the engine finds through
``JambaConfig.served_model``. The cache has three groups of layers: the
attention layers' K and V, paged; the Mamba layers' convolution state, a
slot's last ``mamba_d_conv - 1`` rows of the convolution's input; and their
recurrent state ``[d_state, d_inner]`` a slot, in float32
(``serving/served_model.py``: two state groups, one of its own type).

Layer equations (the plain reference, ``benchmark/families/jamba/
reference.py``, states the same independently; tests hold the two together):

* ``h = E[ids]``; every layer ``h = h + mixer(rms(h))``, ``h = h +
  mlp(rms(h))``; after the last layer ``rms(h)`` and the head, tied to
  ``E``; RMS norms with gains, eps ``rms_norm_eps``;
* layer ``l`` is an attention layer where ``l mod attn_layer_period ==
  attn_layer_offset`` and a Mamba layer elsewhere;
* attention: ``q = x Wq`` as ``num_attention_heads`` heads of ``head_dim``
  (hidden / heads), ``k = x Wk``, ``v = x Wv`` as ``num_key_value_heads``;
  query head j reads KV head ``j // (heads / kv heads)``; no bias, no
  positional encoding; causal softmax at scale ``head_dim^-0.5``; ``Wo``;
* Mamba: ``[x | z] = h W_in`` (``d_inner = mamba_expand x hidden`` each, no
  bias); ``x = silu(conv(x) + b_conv)``, a depthwise causal convolution of
  ``mamba_d_conv`` taps from zeros before the sequence; ``[dt | B | C] = x
  W_x`` (``mamba_dt_rank``, ``mamba_d_state``, ``mamba_d_state``), each
  RMS-normed with a gain of its own; ``dt = softplus(dt W_dt + b_dt)``;
  ``A = -exp(A_log)``; ``s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) B_t``,
  ``y_t = s_t C_t + D x_t`` per channel; ``out = (y * silu(z)) W_out``;
* MLP: ``down(silu(gate x) * up x)`` of ``intermediate_size``.

The tree holds the layers stacked by their mixer's kind, each layer with its
mixer, its MLP and both norms (``mamba``, ``attn``), in the layers' order.
The walk (``walk``) is one scan a RUN of layers of one kind (the published
28: seven Mamba, one attention, thirteen Mamba, one attention, six Mamba):
``models/moe.py::run_layers`` would scan the published period of fourteen
twice, fourteen layers of code in every executable.
"""
from __future__ import annotations

import dataclasses
import itertools
import logging

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas_kernels.paged_mqa import paged_mqa_decode
from ..ops.pallas_kernels.selective_scan import selective_scan
from ..serving import metrics
from ..serving.paged_attention import grouped_attend, latent_scatter, \
    latent_window, pad_lanes, window_mask
from ..serving.served_model import CacheGeometry, CacheGroup, ServedModel
from .moe import F32, compute_of, ffn, final_logits, mm, rms_norm

logger = logging.getLogger("paddle_tpu.jamba")


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    """The published keys (defaults: AI21-Jamba2-3B), hashable so that it
    keys the engine's memoized builders. Not published: ``head_dim`` (None
    is hidden / heads), ``initializer_range``, ``compute_dtype``."""
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    head_dim: int = None
    attn_layer_offset: int = 7
    attn_layer_period: int = 14
    num_experts: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    compute_dtype: str = "float32"

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.hidden_size // self.num_attention_heads)
        if self.num_experts != 1 or self.mamba_proj_bias \
                or not self.mamba_conv_bias:
            raise ValueError(
                "only the dense layout is built: num_experts 1, a "
                "convolution bias and no projection bias (as published for "
                "Jamba2-3B)")

    @classmethod
    def from_dict(cls, d, **over):
        """From a published ``config.json`` dict (other keys ignored)."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        kw.update(over)
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

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    def is_mamba(self, layer):
        return layer % self.attn_layer_period != self.attn_layer_offset

    def layers_of(self, mamba):
        return sum(1 for l in range(self.num_hidden_layers)
                   if self.is_mamba(l) == mamba)


def runs(config):
    """[(is a Mamba layer, how many in a row)] over the layers in order."""
    return [(k, len(list(g))) for k, g in itertools.groupby(
        config.is_mamba(l) for l in range(config.num_hidden_layers))]


# ---------------------------------------------------------------------------
# parameters


def _stack_shapes(c):
    """{stack: {leaf: one layer's shape}}. ``A_log`` is ``[d_state,
    d_inner]``, the published ``[d_inner, d_state]`` turned to the state's
    layout; ``conv_w`` is ``[d_conv, d_inner]``, oldest tap first."""
    H, Di, N, R = c.hidden_size, c.d_inner, c.mamba_d_state, c.mamba_dt_rank
    d, F = c.head_dim, c.intermediate_size
    common = {"mixer_norm_g": (H,), "ffn_norm_g": (H,), "gate_w": (H, F),
              "up_w": (H, F), "down_w": (F, H)}
    return {
        "mamba": {**common, "in_w": (H, 2 * Di),
                  "conv_w": (c.mamba_d_conv, Di), "conv_b": (Di,),
                  "x_w": (Di, R + 2 * N), "dt_norm_g": (R,),
                  "b_norm_g": (N,), "c_norm_g": (N,), "dt_w": (R, Di),
                  "dt_b": (Di,), "A_log": (N, Di), "D": (Di,),
                  "out_w": (Di, H)},
        "attn": {**common, "wq": (H, c.num_attention_heads * d),
                 "wk": (H, c.num_key_value_heads * d),
                 "wv": (H, c.num_key_value_heads * d),
                 "wo": (c.num_attention_heads * d, H)}}


def init_jamba_params(config, key, dtype=F32):
    """The functional tree: ``wte`` (the head is tied to it), ``normf_g``,
    and the stacks ``mamba`` and ``attn``, each over its layers in order.
    Mamba's own initialisation where it has one (arXiv:2312.00752 and its
    code): ``A_log = log(1..d_state)``, ``D = 1``, ``b_dt`` the inverse
    softplus of a dt log-uniform in [1e-3, 1e-1]; the taps and the conv
    bias uniform in +-1/sqrt(d_conv) (a Conv1d's default); gains 1; every
    other matrix N(0, ``initializer_range``)."""
    c = config
    keys = jax.random.split(key, 3)
    std = c.initializer_range
    tree = {"wte": (std * jax.random.normal(
        keys[0], (c.vocab_size, c.hidden_size), F32)).astype(dtype),
        "normf_g": jnp.ones((c.hidden_size,), dtype)}
    for k, (stack, shapes) in zip(keys[1:], sorted(_stack_shapes(c).items())):
        n = c.layers_of(stack == "mamba")
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            shape, ki = (n,) + shape, jax.random.fold_in(k, i)
            if name.endswith("_g") or name == "D":
                a = jnp.ones(shape, F32)
            elif name == "A_log":
                a = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, c.mamba_d_state + 1, dtype=F32))[:, None], shape)
            elif name == "dt_b":
                dt = jnp.exp(jax.random.uniform(
                    ki, shape, F32, np.log(1e-3), np.log(1e-1)))
                a = dt + jnp.log(-jnp.expm1(-dt))
            elif name.startswith("conv_"):
                lim = 1.0 / np.sqrt(c.mamba_d_conv)
                a = jax.random.uniform(ki, shape, F32, -lim, lim)
            else:
                a = std * jax.random.normal(ki, shape, F32)
            out[name] = a.astype(dtype)
        tree[stack] = out
    return tree


# ---------------------------------------------------------------------------
# pieces


def causal_conv(x, before, w, b):
    """The depthwise causal convolution of a window x [B, T, Di] whose ``K -
    1`` rows before it are ``before`` [B, (K - 1) Di] (one row a slot, the
    oldest first), by taps w [K, Di] (oldest first) and bias b: float32
    sums. Returns y [B T, Di] float32 and the window's rows as the state
    keeps them, [B, K - 1 + T, Di]. A one-position window is taken row by
    row, [B, Di] at a time, as the state stores it."""
    B, T, Di = x.shape
    K = w.shape[0]
    wf, bf = w.astype(F32), b.astype(F32)
    before = before.astype(x.dtype)
    if T == 1:
        rows = [before[:, j * Di:(j + 1) * Di] for j in range(K - 1)] \
            + [x[:, 0]]
        y = sum(r.astype(F32) * wf[j] for j, r in enumerate(rows)) + bf
        return y, jnp.stack(rows, axis=1)
    rows = jnp.concatenate([before.reshape(B, K - 1, Di), x], axis=1)
    y = sum(rows[:, j:j + T].astype(F32) * wf[j] for j in range(K))
    return (y + bf).reshape(B * T, Di), rows


def mamba_mixer(p, x, config, conv, scan):
    """The Mamba-1 mixer on the normed input x [B, T, H] (compute type),
    before its residual add, on rows [B T, ...] (a [B, 1, Di] array is a
    sparse tile a row on a TPU). ``conv(xi [B, T, Di])`` -> (y [B T, Di]
    float32, carry) and ``scan(dt, dtx, A, B, C)`` (rows) -> (y [B T, Di]
    float32, carry) are where the plain and the paged forward differ.
    Returns the output [B, T, H] (float32) and both carries."""
    c = config
    B, T, H = x.shape
    compute = x.dtype
    N, R = c.mamba_d_state, c.mamba_dt_rank
    with jax.named_scope("pt_ssm_in"):
        xi, z = jnp.split(mm(x.reshape(B * T, H), p["in_w"]), 2, axis=-1)
        y, conv_carry = conv(xi.reshape(B, T, -1))
        xc = jax.nn.silu(y).astype(compute)
    with jax.named_scope("pt_ssm_scan"):
        dt, bm, cm = jnp.split(mm(xc, p["x_w"]), [R, R + N], axis=-1)
        eps = c.rms_norm_eps
        dt = rms_norm(dt, p["dt_norm_g"], eps)
        bm = rms_norm(bm, p["b_norm_g"], eps).astype(F32)
        cm = rms_norm(cm, p["c_norm_g"], eps).astype(F32)
        dt = jax.nn.softplus(mm(dt, p["dt_w"], F32)
                             + p["dt_b"].astype(F32))
        a = -jnp.exp(p["A_log"].astype(F32))
        xf = xc.astype(F32)
        y, scan_carry = scan(dt, dt * xf, a, bm, cm)
        y = y + p["D"].astype(F32) * xf
    with jax.named_scope("pt_ssm_out"):
        g = y * jax.nn.silu(z.astype(F32))
        out = mm(g.astype(compute), p["out_w"], F32)
    return out.reshape(B, T, H), conv_carry, scan_carry


def _columns(v, B, T):
    """Rows [B T, N] as each row's columns [B, N, T] (the kernels')."""
    return jnp.swapaxes(v.reshape(B, T, -1), 1, 2)


def attention(p, x, config, attend):
    """Multi-query attention on the normed input x [B, T, H] (compute type),
    before its residual add; ``attend(q, k, v)`` -> (ctx [B, T, heads, d],
    carry) with k and v [B, T, kv heads x d]."""
    c = config
    B, T, _ = x.shape
    with jax.named_scope("pt_attn_mqa"):
        q = mm(x, p["wq"]).reshape(B, T, c.num_attention_heads, c.head_dim)
        ctx, carry = attend(q, mm(x, p["wk"]), mm(x, p["wv"]))
        return mm(ctx.reshape(B, T, -1), p["wo"], F32), carry


def mlp(p, h, config):
    with jax.named_scope("pt_mlp"):
        xn = rms_norm(h, p["ffn_norm_g"], config.rms_norm_eps)
        return ffn(xn.astype(compute_of(config)), p["gate_w"], p["up_w"],
                   p["down_w"])


def walk(params, config, carry, layer_fn):
    """``layer_fn(carry, leaves, is_mamba, index) -> carry`` over every layer
    in order: one scan a run of layers of one kind, the layer's leaves
    indexed out of its kind's stack (``index``: its place there)."""
    seen = {True: 0, False: 0}
    for mamba, n in runs(config):
        stack = params["mamba" if mamba else "attn"]

        def body(carry, i, stack=stack, mamba=mamba):
            leaves = jax.tree_util.tree_map(lambda a: a[i], stack)
            return layer_fn(carry, leaves, mamba, i), None

        # what the walk itself costs on a device trace (a layer's leaves
        # indexed out of their stacks) is pt_layers'
        with jax.named_scope("pt_layers"):
            carry, _ = jax.lax.scan(
                body, carry, seen[mamba] + jnp.arange(n, dtype=jnp.int32))
        seen[mamba] += n
    return carry


def _logits(params, config, h):
    """The final norm and the head tied to the embedding."""
    return final_logits({"normf_g": params["normf_g"],
                         "head_w": params["wte"].T}, config, h)


def _lanes(x, width):
    """x [..., d] widened with zeros to ``width`` lanes (the state's)."""
    return pad_lanes(x, jax.ShapeDtypeStruct(x.shape[:-1] + (width,),
                                             x.dtype))


def forward(params, config, ids):
    """The plain causal forward over ids [B, T]: float32 logits [B, T, V]."""
    c = config
    B, T = ids.shape
    compute = compute_of(c)
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    zero = jnp.zeros((B,), jnp.int32)
    slots = jnp.arange(B, dtype=jnp.int32)
    state = jnp.zeros((1, B, c.mamba_d_state, c.d_inner), F32)

    def layer_fn(h, p, mamba, _index):
        x = rms_norm(h, p["mixer_norm_g"], c.rms_norm_eps).astype(compute)
        if mamba:
            before = jnp.zeros((B, (c.mamba_d_conv - 1) * c.d_inner), compute)
            out, _, _ = mamba_mixer(
                p, x, c,
                lambda xi: (causal_conv(xi, before, p["conv_w"],
                                        p["conv_b"])[0], None),
                lambda dt, dtx, a, bm, cm: (selective_scan(
                    state, jnp.int32(0), slots, zero, zero + T, dt, dtx, a,
                    _columns(bm, B, T), _columns(cm, B, T))[0], None))
        else:
            def attend(q, k, v):
                shape = (B, T, c.num_key_value_heads, c.head_dim)
                return grouped_attend(q, k.reshape(shape), v.reshape(shape),
                                      window_mask(pos, pos), compute), None
            out, _ = attention(p, x, c, attend)
        h = h + out
        return h + mlp(p, h, c)

    h = walk(params, c, params["wte"][ids].astype(F32), layer_fn)
    return _logits(params, c, h)


# ---------------------------------------------------------------------------
# serving: the paged forward over pages and states, and the engine's seam


def cache_groups(config):
    """The cache's groups: the attention layers' K and V, paged, a row of
    the KV heads side by side (one head of 128 is a page of whole (16, 128)
    tiles); then the Mamba layers' convolution state, a slot's last ``d_conv
    - 1`` rows of the convolution's input, in the compute type; and their
    recurrent state ``[d_state, d_inner]`` a slot, in float32 (the state is
    summed over thousands of decode steps, as the published kernels keep
    it)."""
    c = config
    return (CacheGroup(("k", "v"), c.layers_of(False),
                       (c.num_key_value_heads * c.head_dim,)),
            CacheGroup(("conv",), c.layers_of(True),
                       ((c.mamba_d_conv - 1) * c.d_inner,), paged=False),
            CacheGroup(("ssm",), c.layers_of(True),
                       (c.mamba_d_state, c.d_inner), paged=False,
                       dtype="float32"))


def paged_forward(params, config, ids, pools, start, valid, table, page_size,
                  use_kernel=False):
    """The fused chunk/decode forward the engine dispatches: ids [B, T] is
    each slot's window at positions start[b].. (valid[b] of them real).
    ``pools`` is K and V ``[attention layers, P, page_size, lanes]``, the
    convolution state ``[Mamba layers, slots, d_conv - 1, d_inner]`` and the
    recurrent state ``[Mamba layers, slots, d_state, d_inner]``; ``table``
    the page table [B, pages] and the slots' numbers [B] twice, in
    ``cache_groups``' order; all of them are the layer scans' carry. An
    attention layer writes its context's pages and gathers them, or, in a
    [B, 1] step with ``use_kernel``, reads the live ones through
    ``paged_mqa_decode``. A Mamba layer
    reads its slots' rows of both states (zero where ``start`` is 0: a new
    occupant), takes ``dt`` 0 past ``valid`` (a pad leaves the recurrent
    state where the last real position left it), keeps the convolution's
    last ``d_conv - 1`` REAL rows, and where ``valid`` is 0 leaves both
    states as they were. Returns logits [B, V] at each slot's last real
    position, the pools, and no statistics."""
    c = config
    table, slots, _ = table
    B, T = ids.shape
    keep, Di = c.mamba_d_conv - 1, c.d_inner
    row = c.num_key_value_heads * c.head_dim
    compute = compute_of(c)
    pos = start[:, None] + jnp.arange(T)[None, :]
    live = jnp.arange(T)[None, :] < valid[:, None]
    fresh, moved = (start == 0)[:, None, None], (valid > 0)[:, None, None]
    last_rows = (valid[:, None] + jnp.arange(keep)[None, :])[:, :, None]
    key_pos = jnp.arange(table.shape[1] * page_size)[None, :]

    def layer_fn(carry, p, mamba, l):
        h, (kc, vc, conv_state, ssm) = carry
        x = rms_norm(h, p["mixer_norm_g"], c.rms_norm_eps).astype(compute)
        if mamba:
            def conv(xi):
                old = conv_state[l, slots]                # [B, lanes]
                y, rows = causal_conv(
                    xi, jnp.where(fresh[:, 0], 0, old[:, :keep * Di]),
                    p["conv_w"], p["conv_b"])
                new = rows[:, 1:] if T == 1 else \
                    jnp.take_along_axis(rows, last_rows, axis=1)
                new = pad_lanes(new.reshape(B, keep * Di).astype(old.dtype),
                                old)
                # a dispatch's slots are consecutive (a slice of the
                # engine's slot numbers): its rows are one block, written
                # in place
                return y, jax.lax.dynamic_update_slice(
                    conv_state, jnp.where(moved[:, 0], new, old)[None],
                    (l, slots[0], jnp.zeros((), slots.dtype)))

            def scan(dt, dtx, a, bm, cm):
                lanes = ssm.shape[-1]
                rows = live.reshape(B * T, 1)
                win = lambda v: _lanes(jnp.where(rows, v, 0.0), lanes)
                y, state = selective_scan(
                    ssm, l, slots, start, valid, win(dt), win(dtx),
                    _lanes(a, lanes), _columns(bm, B, T), _columns(cm, B, T))
                return y[:, :Di], state

            out, conv_state, ssm = mamba_mixer(p, x, c, conv, scan)
        else:
            def gathered(q, kn, vn):
                shape = (B, -1, c.num_key_value_heads, c.head_dim)
                return grouped_attend(
                    q, latent_window(kn, l, table, row).reshape(shape),
                    latent_window(vn, l, table, row).reshape(shape),
                    window_mask(pos, key_pos), compute)

            def attend(q, k, v):
                kn = latent_scatter(kc, l, k, table, pos, valid, page_size)
                vn = latent_scatter(vc, l, v, table, pos, valid, page_size)
                if T > 1 or not use_kernel:
                    return gathered(q, kn, vn), (kn, vn)
                # the [B, 1] read: the live pages alone
                ctx = paged_mqa_decode(q[:, 0], kn, vn, l, table, pos[:, 0],
                                       page_size=page_size)
                return ctx[:, None].astype(compute), (kn, vn)

            out, (kc, vc) = attention(p, x, c, attend)
        h = h + out
        return h + mlp(p, h, c), (kc, vc, conv_state, ssm)

    carry = (params["wte"][ids].astype(F32), tuple(pools))
    h, pools = walk(params, c, carry, layer_fn)
    idx = jnp.maximum(valid - 1, 0)
    last = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]  # [B, H]
    return _logits(params, c, last), pools, None


class _Served(ServedModel):
    """What ``serving.Engine`` asks of this model (``serving/served_model.py``
    states the seam). What is not built for it yet is refused by name at
    construction: prefix sharing among them, for a shared page does not
    bring the Mamba layers' states at its end."""
    name = "jamba"
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
        if jax.default_backend() == "tpu" and config.num_key_value_heads == 1:
            return True
        logger.info(
            "paged decode kernel not taken (jamba): %d KV head under %d "
            "query heads on %s: paged_mqa_decode takes one KV head on a "
            "TPU; the [B, 1] read gathers the table's width",
            config.num_key_value_heads, config.num_attention_heads,
            jax.default_backend())
        return False

    def forward(self, params, config, ids, pools, start, valid, table,
                page_size, use_kernel=False, **_gpt_options):
        return paged_forward(params, config, ids, pools, start, valid, table,
                             page_size, use_kernel)

    def observe(self, kind, valid):
        if kind == "chunk":
            metrics.bump("ssm_scan_positions", int(np.sum(valid)))
        else:
            metrics.bump("ssm_step_slots", int(np.count_nonzero(valid)))


SERVED = _Served()
