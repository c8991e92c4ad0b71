"""The jamba family's plain reference: Mamba-1 selective-scan layers with an
attention layer of one KV head among every few, a dense MLP in every layer,
in straightforward jax.numpy and float32 with every product through ``mm``
(the harness's ``mm_exact`` at ``highest`` precision for the reference,
``mm_fp8`` for the control). No kernels, no cache, no state carried from
anywhere, no batching tricks: a convolution runs over its row from zeros
before the sequence, the recurrence is a sequential ``lax.scan`` over the
row's positions from a zero state, every position attends its causal
prefix. It imports nothing of the program and makes its own weights from the
seed (``weights.py``), in the type they are served in, widened a layer at a
time.

Departures from a textbook listing, each for memory only (at 8,192 positions
the [20, T, T] scores are 5.4 GB beside 2.1 GB of logits): rows are walked
one at a time (``lax.map``), attention one query head at a time, and only
one layer's weights are alive.

The equations, from the published ``config.json`` of ``model_type``
``jamba`` and the family's published modelling code (what the config's keys
do not settle is in the configuration file's ``assumed``):

* ``h = E[ids]``; every layer: ``h = h + mixer(rms(h))``, ``h = h +
  mlp(rms(h))``, RMS norms with gains (``rms_norm_eps``); after the last
  layer ``rms(h)`` and the head, which is ``E`` again (tied);
* layer ``l`` is an attention layer where ``l mod attn_layer_period ==
  attn_layer_offset``, a Mamba layer elsewhere; every MLP is ``down(silu(gate
  x) * up x)`` of ``intermediate_size`` (``num_experts`` 1);
* attention on ``x [T, H]``: ``q = x Wq`` as ``num_attention_heads`` heads of
  ``hidden / heads``, ``k = x Wk``, ``v = x Wv`` as ``num_key_value_heads``;
  query head j reads KV head ``j // (heads / kv heads)``; no positional
  encoding; scores ``q.k / sqrt(head_dim)``, query i sees key j when ``j <=
  i``; then ``Wo``;
* Mamba on ``x [T, H]``: ``[xi | z] = x W_in``; ``xc_t = silu(sum_j w_j
  xi_{t-(K-1)+j} + b)`` per channel (``K = mamba_d_conv``, ``xi`` zero
  before the sequence); ``[dt | B | C] = xc W_x`` (``mamba_dt_rank``,
  ``mamba_d_state`` twice), each RMS-normed with its own gain; ``dt =
  softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; from ``s = 0``:
  ``s_t = exp(dt_t A) * s_{t-1} + (dt_t xc_t) B_t``, ``y_t = sum_n s_t C_t +
  D xc_t``; ``out = (y * silu(z)) W_out``."""
import jax
import jax.numpy as jnp

from benchmark.harness.reference import F32
from benchmark.harness.weights import seed_key

from . import weights as W


def rms(x, eps, g):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def softplus(x):
    return jnp.logaddexp(x, 0.0)


# -- the Mamba mixer ---------------------------------------------------------


def before_sequence(xi, rows):
    """The ``rows`` rows of the convolution's input before position 0:
    zeros."""
    return jnp.zeros((rows, xi.shape[1]), F32)


def taps_of(w):
    """The taps [K, Di] in the order the sum takes them: oldest row first."""
    return w


def ssm_norm(x, eps, g):
    """The RMS norm of dt, B or C (the published dt_layernorm, b_layernorm,
    c_layernorm)."""
    return rms(x, eps, g)


def scan_start(recur, shape):
    """The state before position 0: zeros. ``recur(s0)`` -> (ys, last
    state) runs the row's recurrence from ``s0``."""
    return jnp.zeros(shape, F32)


def advance(s, dA, dBx, t):
    """One position's update of the state [N, Di]."""
    return dA * s + dBx


def conv(xi, w, b):
    """xc [T, Di]: every channel of xi convolved alone with its K taps,
    plus the bias, through silu."""
    T, K = xi.shape[0], w.shape[0]
    rows = jnp.concatenate([before_sequence(xi, K - 1), xi])  # [K - 1 + T]
    w = taps_of(w)
    return silu(sum(w[j] * rows[j:j + T] for j in range(K)) + b)


def mamba(p, x, cfg, mm):
    """The Mamba-1 mixer over one row x [T, H] (already normed)."""
    N, R, eps = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["rms_norm_eps"]
    xi, z = jnp.split(mm(x, p["in_w"]), 2, axis=-1)
    xc = conv(xi, p["conv_w"], p["conv_b"])
    dt, bm, cm = jnp.split(mm(xc, p["x_w"]), [R, R + N], axis=-1)
    dt = softplus(mm(ssm_norm(dt, eps, p["dt_norm_g"]), p["dt_w"])
                  + p["dt_b"])                             # [T, Di]
    bm = ssm_norm(bm, eps, p["b_norm_g"])                  # [T, N]
    cm = ssm_norm(cm, eps, p["c_norm_g"])
    a = -jnp.exp(p["A_log"])                               # [N, Di]

    def recur(s0):
        def position(s, xs):
            t, dt_t, x_t, b_t, c_t = xs
            s = advance(s, jnp.exp(dt_t[None] * a),
                        b_t[:, None] * (dt_t * x_t)[None], t)
            return s, jnp.sum(s * c_t[:, None], axis=0)
        s, ys = jax.lax.scan(position, s0, (jnp.arange(xc.shape[0]), dt, xc,
                                            bm, cm))
        return ys, s

    ys, _ = recur(scan_start(recur, a.shape))
    y = ys + p["D"] * xc
    return mm(y * silu(z), p["out_w"])


# -- attention ----------------------------------------------------------------


def position_code(q, k):
    """What marks a position on q [T, heads, d] and k: nothing (the family
    has no rotary)."""
    return q, k


def kv_head_of(cfg):
    """The KV head that each query head reads."""
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return jnp.arange(cfg["num_attention_heads"]) // group


def attention(p, x, cfg, mm):
    """One attention operator over one row x [T, H] (already normed), one
    query head at a time."""
    T = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  W.head_dim(cfg))
    q, k = position_code(mm(x, p["wq"]).reshape(T, nh, d),
                         mm(x, p["wk"]).reshape(T, nkv, d))
    v = mm(x, p["wv"]).reshape(T, nkv, d)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def one_head(args):
        q_h, kv = args                                     # [T, d], scalar
        s = mm(q_h, k[:, kv].T) / jnp.sqrt(jnp.float32(d))
        s = jnp.where(causal, s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), v[:, kv])

    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2), kv_head_of(cfg)))
    return mm(ctx.transpose(1, 0, 2).reshape(T, nh * d), p["wo"])


# -- a layer --------------------------------------------------------------------


def block(p, h, cfg, mm, mamba_layer):
    """One layer on one row's stream h [T, H]."""
    eps = cfg["rms_norm_eps"]
    x = rms(h, eps, p["mixer_norm_g"])
    h = h + (mamba(p, x, cfg, mm) if mamba_layer
             else attention(p, x, cfg, mm))
    xn = rms(h, eps, p["ffn_norm_g"])
    return h + mm(silu(mm(xn, p["gate_w"])) * mm(xn, p["up_w"]),
                  p["down_w"])


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

        def layer(mamba_layer):
            @jax.jit
            def run(key, l, h):
                p = _f32(W.layer_leaves(cfg, key, l, mamba_layer, dtype))
                return jax.lax.map(
                    lambda x: block(p, x, cfg, mm, mamba_layer), h)
            return run

        @jax.jit
        def head(key, h):
            g = W.top_leaf(cfg, key, "normf_g", dtype).astype(F32)
            wte = W.top_leaf(cfg, key, "wte", dtype).astype(F32)
            return jax.lax.map(
                lambda x: mm(rms(x, cfg["rms_norm_eps"], g), wte.T), h)

        self.embed, self.head = embed, head
        self.layers = {m: layer(m) for m in (False, True)}

    def hidden(self, ids):
        h = self.embed(self.key, ids)
        for l in range(self.cfg["num_hidden_layers"]):
            h = self.layers[W.is_mamba(self.cfg, l)](self.key, jnp.int32(l), h)
        return h


def served_logits(cfg, seed, ids, dtype, mm):
    """float32 logits [n, T, V] of the model whose weights are the seed's,
    stored in ``dtype`` and widened: one full causal forward over ``ids``
    [n, T]."""
    model = _Model(cfg, seed, dtype, mm)
    return model.head(model.key, model.hidden(ids))
