"""The GPT family's plain reference: a pre-LN GPT decoder (multi-head
attention, tanh GELU, biases everywhere), its next-token loss and its
gradients, in straightforward jax.numpy and float32 with every matmul at
``highest`` precision. No kernels, no cache, no batching tricks; it imports
nothing of the program and makes its own weights from the seed
(``weights.py``).

Departures from a textbook listing, each for memory only: the layer stack is
walked with ``lax.scan`` and rows are processed a few at a time, so that the
reference fits beside nothing else on one chip; the backward pass is the
explicit reverse walk over the layers (``jax.vjp`` of one block at a time),
so that gradients are float32 although the stored parameters are bfloat16.

``mm`` is the matrix multiplication every GEMM and both attention products
go through: the harness's ``mm_exact`` for the reference, ``mm_fp8`` for the
control."""
import math

import jax
import jax.numpy as jnp

from benchmark.harness.reference import F32
from benchmark.harness.weights import seed_key

from . import weights as W


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(p, x, cfg, mm):
    """One pre-LN block on x [R, S, H]; p holds float32 leaves."""
    R, S, H = x.shape
    nh = cfg["num_heads"]
    d = H // nh
    eps = cfg["layer_norm_epsilon"]
    h = layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
    qkv = mm(h, p["qkv_w"]) + p["qkv_b"]
    q, k, v = (qkv[..., i * H:(i + 1) * H].reshape(R, S, nh, d)
               .transpose(0, 2, 1, 3) for i in range(3))      # [R, nh, S, d]
    s = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    ctx = mm(a, v).transpose(0, 2, 1, 3).reshape(R, S, H)
    x = x + mm(ctx, p["out_w"]) + p["out_b"]
    h = layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
    up = gelu_tanh(mm(h, p["up_w"]) + p["up_b"])
    return x + mm(up, p["down_w"]) + p["down_b"]


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


# ---------------------------------------------------------------------------
# serving: logits at every position of given rows


def served_logits(cfg, seed, ids, dtype, mm):
    """float32 logits [n, T, V] of the model whose weights are the seed's,
    stored in ``dtype`` and widened: one full causal forward over ``ids``
    [n, T], layer by layer so that only one layer's weights are alive."""
    key = seed_key(seed)
    dtype = jnp.dtype(dtype)

    @jax.jit
    def embed(key, ids):
        wte = W.top_leaf(cfg, key, "wte", dtype).astype(F32)
        wpe = W.top_leaf(cfg, key, "wpe", dtype).astype(F32)
        return wte[ids] + wpe[None, :ids.shape[1]]

    @jax.jit
    def layer(key, l, x):
        return block(_f32(W.layer_leaves(cfg, key, l, dtype)), x, cfg, mm)

    @jax.jit
    def head(key, x):
        g = W.top_leaf(cfg, key, "lnf_g", dtype).astype(F32)
        b = W.top_leaf(cfg, key, "lnf_b", dtype).astype(F32)
        hw = W.top_leaf(cfg, key, "head_w", dtype).astype(F32)
        return mm(layer_norm(x, g, b, cfg["layer_norm_epsilon"]), hw)

    x = embed(key, ids)
    for l in range(cfg["num_layers"]):
        x = layer(key, jnp.int32(l), x)
    return head(key, x)


# ---------------------------------------------------------------------------
# training: loss, float32 gradients, AdamW


def loss_and_grads(params, ids, cfg, mm, rows):
    """Mean next-token cross-entropy over ids [B, S] and its float32
    gradients for the (bfloat16 or float32) ``params`` tree, ``rows`` rows at
    a time inside every layer. Traceable."""
    B, S = ids.shape
    n = B // rows
    H = cfg["hidden_size"]
    eps = cfg["layer_norm_epsilon"]
    count = B * (S - 1)

    wte, wpe = params["wte"].astype(F32), params["wpe"].astype(F32)
    x0 = wte[ids] + wpe[None, :S]

    def chunks(x):
        return x.reshape((n, rows) + x.shape[1:])

    def fwd(x, p_l):
        p32 = _f32(p_l)
        y = jax.lax.map(lambda xc: block(p32, xc, cfg, mm), chunks(x))
        return y.reshape(x.shape), x                 # keeps the layer's input

    xL, xs = jax.lax.scan(fwd, x0, params["blocks"])

    def head_loss(head, xc, idc):
        g, b, hw = head
        logits = mm(layer_norm(xc, g, b, eps), hw)[:, :-1]
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, idc[:, 1:, None], axis=-1)[..., 0]
        return jnp.sum(logz - gold) / count

    head = (params["lnf_g"].astype(F32), params["lnf_b"].astype(F32),
            params["head_w"].astype(F32))

    def head_chunk(acc, xi):
        xc, idc = xi
        l, (gh, gx) = jax.value_and_grad(head_loss, argnums=(0, 1))(
            head, xc, idc)
        loss, gacc = acc
        return (loss + l, jax.tree_util.tree_map(jnp.add, gacc, gh)), gx

    zero_head = jax.tree_util.tree_map(jnp.zeros_like, head)
    (loss, g_head), gxL = jax.lax.scan(
        head_chunk, (jnp.zeros((), F32), zero_head),
        (chunks(xL), chunks(ids)))
    gxL = gxL.reshape(xL.shape)

    def bwd(gx, layer_in):
        p_l, x_l = layer_in
        p32 = _f32(p_l)

        def one(gp_acc, ci):
            xc, gc = ci
            _, vjp = jax.vjp(lambda p, x: block(p, x, cfg, mm), p32, xc)
            gp, gxc = vjp(gc)
            return jax.tree_util.tree_map(jnp.add, gp_acc, gp), gxc

        gp, gx_prev = jax.lax.scan(
            one, jax.tree_util.tree_map(jnp.zeros_like, p32),
            (chunks(x_l), chunks(gx)))
        return gx_prev.reshape(gx.shape), gp

    gx0, g_blocks = jax.lax.scan(bwd, gxL, (params["blocks"], xs),
                                 reverse=True)
    g_wte = jnp.zeros_like(wte).at[ids].add(gx0)
    g_wpe = jnp.zeros_like(wpe).at[:S].add(jnp.sum(gx0, axis=0))
    grads = {"wte": g_wte, "wpe": g_wpe, "lnf_g": g_head[0],
             "lnf_b": g_head[1], "head_w": g_head[2], "blocks": g_blocks}
    return loss, grads


def decays(path_names):
    """AdamW's decoupled decay goes to matrices only: not to biases, gains
    or the position table (the configuration's trainer states this rule)."""
    leaf = path_names[-1]
    return not (leaf.endswith("_b") or leaf.endswith("_g") or leaf == "wpe")
