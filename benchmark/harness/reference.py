"""What every family's plain reference shares: the matrix multiplication
that a reference's products go through, exact and in the control's
precision, and the configuration's trainer stated plainly (AdamW with
global-norm clipping) around a family's loss and gradients. The model itself
is the family's (``families/<family>/reference.py``); nothing here knows a
block's shapes or imports the program.

``mm_exact`` is the reference: float32 at ``highest`` precision. ``mm_fp8``
is the control: the precision below bfloat16 that would tempt a later PR,
float8 e4m3 with a scale for each vector along the contraction (int8 with
such a scale carries as many bits as bfloat16 does: its readings could not be
told from the program's; PERF.md, section 7)."""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

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


class TrainReference:
    """The configuration's trainer, plainly: parameters and both moments
    stored in the configuration's types, every computation in float32.
    Moments rest on the host between steps so that the float32 gradients fit
    on the chip beside the parameters."""

    def __init__(self, family, cfg, seed, hp, rows, mm=mm_exact,
                 param_dtype="bfloat16", moment_dtype="bfloat16"):
        """``family`` gives the model: ``weights.make_weights``,
        ``reference.loss_and_grads`` and ``reference.decays``."""
        self.cfg, self.hp = cfg, hp
        self.moment_dtype = jnp.dtype(moment_dtype)
        self.params = family.weights.make_weights(cfg, seed, param_dtype)
        self.decays = family.reference.decays
        self.m = self.v = None
        self.t = 0
        self.seconds = []      # (gradients, update) of each step
        self._lg = jax.jit(functools.partial(
            family.reference.loss_and_grads, cfg=cfg, mm=mm, rows=rows))
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
                jnp.asarray(self.t, F32), decay=self.decays(names))
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

