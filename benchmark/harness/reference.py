"""The plain reference: a pre-LN GPT decoder, its next-token loss, its
gradients and AdamW with global-norm clipping, in straightforward jax.numpy
and float32 with every matmul at ``highest`` precision. No kernels, no cache,
no batching tricks; it imports nothing of the program and makes its own
weights from the seed (``weights.py``).

Departures from a textbook listing, each for memory only: the layer stack is
walked with ``lax.scan`` and rows are processed a few at a time, so that the
reference fits beside nothing else on one chip; the backward pass is the
explicit reverse walk over the layers (``jax.vjp`` of one block at a time),
so that gradients are float32 although the stored parameters are bfloat16.

``mm`` is the matrix multiplication every GEMM and both attention products
go through. ``mm_exact`` is the reference. ``mm_fp8`` is the control: the
precision below bfloat16 that would tempt a later PR, float8 e4m3 with a
scale for each vector along the contraction (int8 with such a scale carries
as many bits as bfloat16 does: its readings could not be told from the
program's; PERF.md, section 7)."""
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def mm_exact(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _fake_fp8(a, axis):
    """Rounds to float8 e4m3 (4 significant bits, exponents down to 2**-6,
    subnormals below) after scaling each vector along ``axis`` to the
    format's largest value, 448; written out in float32 arithmetic."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    x = a / s
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(x), 2.0 ** -20)))
    ulp = jnp.exp2(jnp.maximum(e, -6.0) - 3.0)
    q = jnp.round(x / ulp) * ulp * s
    return a + jax.lax.stop_gradient(q - a)      # straight-through


def mm_fp8(a, b):
    return jnp.matmul(_fake_fp8(a, -1), _fake_fp8(b, -2), precision=HIGHEST)


MATMULS = {"exact": mm_exact, "fp8": mm_fp8}


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


def served_logits(cfg, seed, ids, dtype, mm=mm_exact):
    """float32 logits [n, T, V] of the model whose weights are the seed's,
    stored in ``dtype`` and widened: one full causal forward over ``ids``
    [n, T], layer by layer so that only one layer's weights are alive."""
    key = W.seed_key(seed)
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


class TrainReference:
    """The configuration's trainer, plainly: parameters and both moments
    stored in the configuration's types, every computation in float32.
    Moments rest on the host between steps so that the float32 gradients fit
    on the chip beside the parameters."""

    def __init__(self, cfg, seed, hp, rows, mm=mm_exact, param_dtype="bfloat16",
                 moment_dtype="bfloat16"):
        self.cfg, self.hp = cfg, hp
        self.moment_dtype = jnp.dtype(moment_dtype)
        self.params = W.make_weights(cfg, seed, param_dtype)
        self.m = self.v = None
        self.t = 0
        self.seconds = []      # (gradients, update) of each step
        self._lg = jax.jit(functools.partial(loss_and_grads, cfg=cfg, mm=mm,
                                             rows=rows))
        self._upd = jax.jit(self._update, static_argnames=("decay",),
                            donate_argnums=(0, 1))

    def _update(self, p, g, m, v, scale, t, decay):
        hp = self.hp
        b1, b2 = hp["beta1"], hp["beta2"]
        g = g * scale
        m = b1 * m.astype(F32) + (1 - b1) * g
        v = b2 * v.astype(F32) + (1 - b2) * jnp.square(g)
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        p32 = p.astype(F32)
        if decay:
            p32 = p32 * (1 - hp["lr"] * hp["weight_decay"])
        p32 = p32 - hp["lr"] * mhat / (jnp.sqrt(vhat) + hp["epsilon"])
        return (p32.astype(p.dtype), m.astype(self.moment_dtype),
                v.astype(self.moment_dtype))

    def step(self, ids, leaf_sq_norms=None, last=False):
        """One optimizer step on ids [B, S]; returns the loss before it.
        ``leaf_sq_norms(tree)`` is applied to the gradients as the optimizer
        gets them (clipped), its result kept in ``self.grad_sq``. After the
        ``last`` step the moments are not brought back to the host."""
        t0 = time.perf_counter()
        loss, grads = self._lg(self.params, jnp.asarray(ids))
        jax.block_until_ready(loss)
        t1 = time.perf_counter()
        flat_g, treedef = jax.tree_util.tree_flatten_with_path(grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for _, g in flat_g))
        clip = self.hp.get("clip_global_norm")
        scale = jnp.minimum(clip / jnp.maximum(gnorm, 1e-12), 1.0) \
            if clip else jnp.ones((), F32)
        if leaf_sq_norms is not None:
            s2 = jnp.square(scale)
            self.grad_sq = {k: v * s2 for k, v in leaf_sq_norms(grads).items()}
        self.t += 1
        flat_p = jax.tree_util.tree_leaves(self.params)
        if self.m is None:
            self.m = [np.zeros(p.shape, self.moment_dtype) for p in flat_p]
            self.v = [np.zeros(p.shape, self.moment_dtype) for p in flat_p]
        new_p = []
        for i, ((path, g), p) in enumerate(zip(flat_g, flat_p)):
            names = [getattr(k, "key", str(k)) for k in path]
            sh = p.sharding
            p2, m2, v2 = self._upd(
                p, g, jax.device_put(self.m[i], sh),
                jax.device_put(self.v[i], sh), scale,
                jnp.asarray(self.t, F32), decay=decays(names))
            if not last:
                self.m[i], self.v[i] = np.asarray(m2), np.asarray(v2)
            new_p.append(p2)
            del g
        del grads, flat_g
        self.params = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self.params), new_p)
        jax.block_until_ready(new_p)
        self.seconds.append((t1 - t0, time.perf_counter() - t1))
        return float(loss)

