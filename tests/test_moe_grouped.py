"""The expert layer's grouped form (``models/moe.py::moe_ffn``): each token
multiplied by the experts it chose, its pairs sorted by expert, one grouped
product a matrix over the experts that hold rows. Held against a dense
einsum over every held expert (the form it replaced), written here; and the
TPU's Pallas product (interpret mode) against the ``ragged_dot`` of every
other backend."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import moe as MOE
from paddle_tpu.ops.pallas_kernels.grouped_matmul import grouped_ffn

E, K, H, F = 8, 3, 32, 16
HIGHEST = jax.lax.Precision.HIGHEST


def _config(dtype, held=(0, E)):
    return types.SimpleNamespace(
        n_routed_experts=E, num_experts_per_tok=K, norm_topk_prob=True,
        route_norm_eps=1e-20, routed_scaling_factor=2.5, held=held,
        rms_norm_eps=1e-6, compute_dtype=dtype)


def _layer(seed, layers=None):
    """One layer's leaves (``layers`` None) or a stack of ``layers``; the
    router never picks experts 6 and 7: they get no rows."""
    ks = jax.random.split(jax.random.key(seed), 8)
    lead = () if layers is None else (layers,)

    def n(k, *shape, s=0.3):
        return s * jax.random.normal(k, lead + shape, jnp.float32)

    bias = jnp.where(jnp.arange(E) >= 6, -1e4, 0.0)
    return {"ffn_norm_g": 1.0 + n(ks[0], H, s=0.1), "router_w": n(ks[1], H, E),
            "router_bias": jnp.broadcast_to(bias, lead + (E,)),
            "experts_gate_w": n(ks[2], E, H, F),
            "experts_up_w": n(ks[3], E, H, F),
            "experts_down_w": n(ks[4], E, F, H),
            "shared_gate_w": n(ks[5], H, F), "shared_up_w": n(ks[6], H, F),
            "shared_down_w": n(ks[7], F, H)}


def _dense(p, x, c, mask, held, shared):
    """Every token through every held expert, weighed by its routing weight
    (nought where the expert was not chosen): float32, HIGHEST."""
    lo, hi = held
    B, T, _ = x.shape
    xn = MOE.rms_norm(x, p["ffn_norm_g"], c.rms_norm_eps).reshape(B * T, H)
    idx, w = MOE.moe_route(xn, p["router_w"], p["router_bias"], c)
    combine = jnp.einsum("nk,nke->ne", w, jax.nn.one_hot(idx, E))[:, lo:hi]
    if mask is not None:
        combine = combine * mask.reshape(-1, 1)

    def ein(s, a, b):
        return jnp.einsum(s, a, b, precision=HIGHEST)

    gate = ein("nh,ehf->enf", xn, p["experts_gate_w"][lo:hi])
    up = ein("nh,ehf->enf", xn, p["experts_up_w"][lo:hi])
    act = jax.nn.silu(gate) * up * combine.T[:, :, None]
    y = ein("enf,efh->nh", act, p["experts_down_w"][lo:hi])
    if shared:
        act = jax.nn.silu(ein("nh,hf->nf", xn, p["shared_gate_w"])) \
            * ein("nh,hf->nf", xn, p["shared_up_w"])
        y = y + ein("nf,fh->nh", act, p["shared_down_w"])
    return y.reshape(B, T, H)


MASKS = {"all": None,
         "dropped": jnp.arange(7)[None] < jnp.array([[4], [7]])}


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("shared", [True, False])
@pytest.mark.parametrize("held", [(0, E), (2, 7)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_grouped_form_matches_the_dense_reference(dtype, held, shared, mask):
    """2 x 7 tokens, top-3 of 8: 42 pairs (no multiple of the product's row
    tile), experts 6 and 7 with no rows, a held sub-range, dropped tokens."""
    c = _config(dtype)
    p = _layer(0)
    x = jax.random.normal(jax.random.key(1), (2, 7, H), jnp.float32)
    y, stats = jax.jit(lambda p, x: MOE.moe_ffn(
        p, x, c, MASKS[mask], held=held, shared=shared))(p, x)
    ref = _dense(p, x, c, MASKS[mask], held, shared)
    scale = float(jnp.max(jnp.abs(ref)))
    tol = 2e-5 if dtype == "float32" else 2e-2
    assert float(jnp.max(jnp.abs(y - ref))) <= tol * scale
    if mask == "dropped":           # a dropped token gets the shared part only
        assert float(jnp.max(jnp.abs(y[0, 4:] - ref[0, 4:]))) <= tol * scale
    assert int(stats[1]) <= min(6, held[1]) - held[0]    # 6, 7 got no rows


def test_a_layer_read_off_the_stack_is_the_layer_alone():
    """``layer_leaves`` keeps the expert stacks whole with the layer's index;
    the grouped product reads that layer's experts off them."""
    c = _config("float32")
    stack = _layer(2, layers=3)
    x = jax.random.normal(jax.random.key(3), (1, 5, H), jnp.float32)
    leaves = MOE.layer_leaves(stack, jnp.int32(1))
    assert leaves["experts_gate_w"].shape == (3, E, H, F)
    alone = jax.tree_util.tree_map(lambda a: a[1], stack)
    whole, s1 = jax.jit(lambda p: MOE.moe_ffn(p, x, c))(leaves)
    one, s2 = jax.jit(lambda p: MOE.moe_ffn(p, x, c))(alone)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(one), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_no_token_kept_gives_the_shared_expert_alone():
    c = _config("float32")
    p = _layer(4)
    x = jax.random.normal(jax.random.key(5), (2, 3, H), jnp.float32)
    mask = jnp.zeros((2, 3), bool)
    y, stats = MOE.moe_ffn(p, x, c, mask)
    ref = _dense(p, x, c, mask, (0, E), True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)
    assert stats.tolist() == [0, 0, 0]


@pytest.mark.parametrize("rows", [5, 40, 200])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_tpu_product_is_ragged_dot(dtype, rows):
    """The Pallas product (interpret mode) against the backends' ragged_dot,
    at layer 1 of three and experts 2..7 of 8: rows no multiple of the row
    tile, several row tiles (200), groups with no rows, rows past the
    groups' sum (which neither computes)."""
    rng = np.random.default_rng(rows)
    L = 3
    w = [jnp.asarray(0.1 * rng.normal(size=s), dtype)
         for s in ((L, E, H, F), (L, E, H, F), (L, E, F, H))]
    sizes = rng.multinomial(rows - rows // 5, np.ones(5) / 5)
    gs = jnp.asarray(np.insert(sizes, 2, 0), jnp.int32)  # 6, one empty
    x = jnp.asarray(rng.normal(size=(rows, H)), dtype)
    a = grouped_ffn(x, *w, gs, jnp.int32(1), 2, interpret=True)
    b = MOE._ragged_ffn(x, *w, gs, jnp.int32(1), 2)
    n = int(gs.sum())
    np.testing.assert_allclose(np.asarray(a[:n]), np.asarray(b[:n]),
                               rtol=1e-5, atol=1e-6)


def test_the_tpu_product_with_no_rows_stores_nothing_and_runs():
    """An idle dispatch: every pair dropped, one grid step that stores no
    row (the grid is never empty)."""
    w = [jnp.ones(s, jnp.float32) for s in ((2, E, H, F), (2, E, H, F),
                                            (2, E, F, H))]
    x = jnp.ones((12, H), jnp.float32)
    out = grouped_ffn(x, *w, jnp.zeros(E, jnp.int32), jnp.int32(0), 0,
                      interpret=True)
    assert out.shape == (12, H) and out.dtype == jnp.float32
