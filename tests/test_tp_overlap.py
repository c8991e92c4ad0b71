"""Tensor-parallel compute/communication overlap + sequence parallelism
(distributed/tp_overlap.py) on the 8-virtual-device CPU mesh: ring-kernel
parity, GPT-mini mp=4 loss parity vs the GSPMD baseline over 20 steps,
flags-off bitwise trajectory invariance, mp comm counters (RS+AG replacing
the per-block all-reduces), 1/mp activation claim, mp_layers wiring, the
grad_comm dp x mp composition, and the satellite fixes (split validation,
ParallelCrossEntropy, DataLoader prefetch_factor)."""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed import env as dist_env
from paddle_tpu.distributed import tp_overlap as tp
from paddle_tpu.models.gpt import GPTConfig, gpt_block_fn
from paddle_tpu.models.gpt_hybrid import HybridTrainStep, init_gpt_params, \
    gpt_hidden

# the kernels and ring schedules under test return per-device values under
# replicated out_specs: replication checking off, as at their call sites
shard_map = functools.partial(jax.shard_map, check_vma=False)


_DEF = {
    "FLAGS_sequence_parallel": False,
    "FLAGS_mp_overlap": False,
    "FLAGS_grad_comm": "auto",
    "FLAGS_weight_update_sharding": False,
    "FLAGS_allreduce_dtype": "float32",
}

SP = {"FLAGS_sequence_parallel": True}
SPOV = {"FLAGS_sequence_parallel": True, "FLAGS_mp_overlap": True}


@pytest.fixture(autouse=True)
def _reset(devices8):
    yield
    paddle.set_flags(dict(_DEF))
    dist_env.set_mesh(None)
    tp.reset_mp_counters()


def _mini_cfg(layers=2, heads=4, hidden=64):
    return GPTConfig(vocab_size=512, hidden_size=hidden, num_layers=layers,
                     num_heads=heads, max_seq_len=64,
                     compute_dtype="float32", use_flash=False, remat=True,
                     dropout=0.0)


def _gpt_run(flags, steps=5, dp=2, mp=4, batch=8, seq=32, seed=0):
    paddle.set_flags(dict(_DEF))
    paddle.set_flags(flags)
    tp.reset_mp_counters()
    mesh = dist_env.create_hybrid_mesh(dp=dp, mp=mp)
    cfg = _mini_cfg()
    opt = paddle.optimizer.AdamW(1e-3)
    step = HybridTrainStep(cfg, opt, mesh=mesh, seed=seed)
    ids = jax.random.randint(jax.random.key(0), (batch, seq), 0,
                             cfg.vocab_size, jnp.int32)
    losses = [float(step(ids)) for _ in range(steps)]
    counters = tp.mp_counters()
    params = jax.tree_util.tree_map(lambda a: np.asarray(jax.device_get(a)),
                                    step.params)
    dist_env.set_mesh(None)
    return losses, counters, params, step


# ---------------------------------------------------------------------------
# ring kernels: fused AG+GEMM / GEMM+RS parity incl. gradients


def test_ring_kernels_match_dense_fwd_and_grad(devices8):
    mp = 4
    mesh = Mesh(np.array(jax.devices()[:mp]), ("mp",))
    B, S, H, F = 2, 8, 16, 32
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, S // mp, H).astype(np.float32))  # per-shape
    xfull = jnp.asarray(rng.randn(B, S, H).astype(np.float32))
    w1 = jnp.asarray(rng.randn(H, F).astype(np.float32) * 0.2)
    w2 = jnp.asarray(rng.randn(F, H).astype(np.float32) * 0.2)


    def f(xf, w1, w2):
        up = tp.ring_ag_gemm(xf, w1, "mp", mp)
        up = jax.nn.gelu(up)
        return tp.gemm_ring_rs(up, w2, "mp", mp)

    smap = shard_map(f, mesh=mesh,
                            in_specs=(P(None, "mp", None), P(None, "mp"),
                                      P("mp", None)),
                            out_specs=P(None, "mp", None))

    def loss_sp(xf, w1, w2):
        return jnp.sum(smap(xf, w1, w2) ** 2)

    def loss_ref(xf, w1, w2):
        return jnp.sum((jax.nn.gelu(xf @ w1) @ w2) ** 2)

    with mesh:
        v1, g1 = jax.jit(jax.value_and_grad(loss_ref, argnums=(1, 2)))(
            xfull, w1, w2)
        v2, g2 = jax.jit(jax.value_and_grad(loss_sp, argnums=(1, 2)))(
            xfull, w1, w2)
    np.testing.assert_allclose(float(v1), float(v2), rtol=2e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-5)


def test_seq_ag_rs_roundtrip(devices8):
    mp = 4
    mesh = Mesh(np.array(jax.devices()[:mp]), ("mp",))
    x = jnp.arange(2 * 8 * 4, dtype=jnp.float32).reshape(2, 8, 4)

    def f(xs):
        full = tp.seq_all_gather(xs, "mp", mp)
        return tp.seq_reduce_scatter(full, "mp", mp) / mp

    smap = shard_map(f, mesh=mesh, in_specs=P(None, "mp", None),
                            out_specs=P(None, "mp", None))
    with mesh:
        out = jax.jit(smap)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=1e-6)


# ---------------------------------------------------------------------------
# head-major qkv storage is a pure relabeling


def test_qkv_head_major_is_bitwise_relabeling(devices8):
    cfg = _mini_cfg()
    params = init_gpt_params(cfg, jax.random.key(3))
    x = jnp.asarray(np.random.RandomState(0).randn(2, 16, cfg.hidden_size)
                    .astype(np.float32))
    layer = {k: v[0] for k, v in params["blocks"].items()}
    ref = gpt_block_fn(cfg)(layer, x)

    hm_blocks = tp.to_qkv_head_major(params["blocks"], cfg.hidden_size,
                                     cfg.num_heads)
    cfg_hm = _mini_cfg()
    cfg_hm.qkv_head_major = True
    out = gpt_block_fn(cfg_hm)({k: v[0] for k, v in hm_blocks.items()}, x)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


# ---------------------------------------------------------------------------
# GPT-mini mp=4: loss parity vs the GSPMD baseline over 20 steps


def test_seq_parallel_matches_gspmd_20_steps(devices8):
    base, cb, pb, _ = _gpt_run({}, steps=20)
    sp, cs, ps, _ = _gpt_run(SP, steps=20)
    np.testing.assert_allclose(base, sp, rtol=5e-4, atol=1e-5)
    assert cb["steps"] == 0 and cs["steps"] == 20


def test_seq_parallel_plus_overlap_matches_gspmd_20_steps(devices8):
    base, _, _, _ = _gpt_run({}, steps=20)
    ov, co, _, _ = _gpt_run(SPOV, steps=20)
    np.testing.assert_allclose(base, ov, rtol=5e-4, atol=1e-5)
    assert co["ppermute_hops"] > 0


def test_flags_off_trajectory_bitwise_unchanged(devices8):
    """With both flags OFF the step must be byte-identical to the seed path:
    running the explicit schedule in between must not perturb a fresh
    flags-off trajectory (same seed, same data)."""
    _, _, p1, _ = _gpt_run({}, steps=3)
    _gpt_run(SPOV, steps=1)            # build + run the explicit schedule
    _, c3, p3, _ = _gpt_run({}, steps=3)
    assert c3["steps"] == 0
    jax.tree_util.tree_map(np.testing.assert_array_equal, p1, p3)


# ---------------------------------------------------------------------------
# counters: per-block mp collectives replaced by RS+AG (counter-gated)


def test_counters_rs_ag_replace_per_block_allreduces(devices8):
    steps, L, mp = 4, 2, 4
    _, c, _, step = _gpt_run(SP, steps=steps)
    # 4 collectives per block per step: AG(qkv), RS(out), AG(up), RS(down)
    assert c["collectives"] == steps * 4 * L
    assert c["rs_bytes"] == c["ag_bytes"] > 0
    assert c["ppermute_hops"] == 0
    base = tp.gspmd_baseline_record(step.config, mp, 8, 32)
    assert base.collectives == 2 * L
    # same wire bytes as the all-reduce pair (ring AR = RS+AG)
    assert c["rs_bytes"] + c["ag_bytes"] == \
        steps * base.bytes_by_kind["all_reduce"]


def test_counters_overlap_ring_hops(devices8):
    steps, L, mp = 3, 2, 4
    _, c, _, _ = _gpt_run(SPOV, steps=steps)
    assert c["ppermute_hops"] == steps * 4 * L * (mp - 1)


def test_activation_bytes_between_blocks_reduced_by_mp(devices8):
    mp = 4
    _, c, _, step = _gpt_run(SP, steps=1)
    base = tp.gspmd_baseline_record(step.config, mp, 8, 32)
    assert c["activation_bytes"] * mp == base.activation_bytes
    assert c["activation_bytes"] == 8 * (32 // mp) * 64 * 4  # B*(S/mp)*H*f32


def test_overlap_hlo_contains_ppermute_and_off_does_not(devices8):
    mesh = dist_env.create_hybrid_mesh(dp=2, mp=4)
    ids = jnp.zeros((8, 32), jnp.int32)

    def lowered_text(flags):
        paddle.set_flags(dict(_DEF))
        paddle.set_flags(flags)
        cfg = _mini_cfg()
        params = init_gpt_params(cfg, jax.random.key(0))
        if flags.get("FLAGS_sequence_parallel"):
            params["blocks"] = tp.to_qkv_head_major(
                params["blocks"], cfg.hidden_size, cfg.num_heads)
            cfg.qkv_head_major = True
        fn = jax.jit(lambda p, i: gpt_hidden(p, i, cfg, mesh))
        return fn.lower(params, ids).compile().as_text()

    off = lowered_text({})
    on = lowered_text(SPOV)
    assert "collective-permute" not in off
    assert "collective-permute" in on


# ---------------------------------------------------------------------------
# resolve gating / fallback rules


def test_resolve_gates(devices8):
    cfg = _mini_cfg()
    cfg.qkv_head_major = True
    mesh = dist_env.create_hybrid_mesh(dp=2, mp=4)
    paddle.set_flags(dict(_DEF))
    assert tp.resolve_gpt(cfg, mesh) is None                 # flags off
    paddle.set_flags({"FLAGS_mp_overlap": True})
    assert tp.resolve_gpt(cfg, mesh) is None                 # overlap w/o sp
    paddle.set_flags({"FLAGS_sequence_parallel": True,
                      "FLAGS_mp_overlap": False})
    got = tp.resolve_gpt(cfg, mesh, batch=8, seq=32)
    assert got is not None and got.n == 4 and not got.overlap
    paddle.set_flags(SPOV)
    assert tp.resolve_gpt(cfg, mesh, batch=8, seq=32).overlap
    assert tp.resolve_gpt(cfg, None) is None                 # no mesh
    assert tp.resolve_gpt(cfg, mesh, batch=8, seq=30) is None  # seq % mp
    cfg5 = _mini_cfg(heads=5, hidden=80)
    cfg5.qkv_head_major = True
    assert tp.resolve_gpt(cfg5, mesh) is None                # heads % mp
    cfg_nohm = _mini_cfg()
    assert tp.resolve_gpt(cfg_nohm, mesh) is None            # logical qkv
    dist_env.set_mesh(None)
    mesh_pp = dist_env.create_hybrid_mesh(dp=1, mp=4, pp=2)
    assert tp.resolve_gpt(cfg, mesh_pp) is None              # pp active


# ---------------------------------------------------------------------------
# mp_layers wiring: seq-parallel constraints and the explicit overlap path


def _mp_layer_model(H=32, inner=64):
    paddle.seed(11)
    from paddle_tpu.distributed.fleet.mp_layers import (
        ColumnParallelLinear, RowParallelLinear)
    return nn.Sequential(
        ColumnParallelLinear(H, inner, gather_output=False),
        nn.GELU(),
        RowParallelLinear(inner, H, input_is_parallel=True))


def _mp_layer_losses(flags, dp=1, mp=4, steps=3):
    paddle.set_flags(dict(_DEF))
    paddle.set_flags(flags)
    mesh = dist_env.create_hybrid_mesh(dp=dp, mp=mp)
    m = _mp_layer_model()
    opt = paddle.optimizer.AdamW(0.01, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, nn.MSELoss(), opt, mesh=mesh)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 8, 32)).astype(np.float32)
    y = rng.standard_normal((4, 8, 32)).astype(np.float32)
    losses = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)).numpy())
              for _ in range(steps)]
    dist_env.set_mesh(None)
    return losses


def test_mp_layers_seq_parallel_constraint_parity(devices8):
    base = _mp_layer_losses({})
    seq = _mp_layer_losses(SP)
    np.testing.assert_allclose(base, seq, rtol=1e-4, atol=1e-6)


def test_mp_layers_explicit_overlap_parity(devices8):
    base = _mp_layer_losses({})
    ov = _mp_layer_losses(SPOV)
    np.testing.assert_allclose(base, ov, rtol=1e-4, atol=1e-6)


def test_layer_schedule_modes(devices8):
    mesh = dist_env.create_hybrid_mesh(dp=2, mp=4)
    paddle.set_flags(dict(_DEF))
    assert tp.layer_schedule(mesh) == "gspmd"
    paddle.set_flags(SP)
    assert tp.layer_schedule(mesh) == "seq"
    paddle.set_flags(SPOV)
    assert tp.layer_schedule(mesh) == "explicit"
    assert tp.layer_schedule(None) == "gspmd"


# ---------------------------------------------------------------------------
# grad_comm composition: explicit dp schedule on a dp x mp mesh


def _comp_model():
    paddle.seed(7)
    from paddle_tpu.distributed.fleet.mp_layers import (
        ColumnParallelLinear, RowParallelLinear)
    return nn.Sequential(
        ColumnParallelLinear(16, 32, gather_output=False),
        nn.ReLU(),
        RowParallelLinear(32, 16, input_is_parallel=True),
        nn.Linear(16, 8))


def _comp_train(flags, steps=3, k=1):
    paddle.set_flags(dict(_DEF))
    paddle.set_flags(flags)
    mesh = dist_env.create_hybrid_mesh(dp=2, mp=4)
    m = _comp_model()
    opt = paddle.optimizer.AdamW(0.01, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, nn.MSELoss(), opt, mesh=mesh,
                                accumulate_steps=k)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 16)).astype(np.float32)
    y = rng.standard_normal((8, 8)).astype(np.float32)
    losses = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)).numpy())
              for _ in range(steps)]
    p = {n: np.asarray(a) for n, a in step.params.items()}
    dist_env.set_mesh(None)
    return p, losses, step


def test_grad_comm_composes_with_mp_axis(devices8):
    p_def, _, st0 = _comp_train({})
    assert st0._gc_cfg is None
    p_rs, _, st = _comp_train({"FLAGS_grad_comm": "on",
                               "FLAGS_weight_update_sharding": True})
    assert st._gc_cfg is not None and st._gc_cfg.auto_axes == ("mp",)
    p_ar, _, _ = _comp_train({"FLAGS_grad_comm": "on"})
    for n in p_def:
        np.testing.assert_allclose(p_ar[n], p_rs[n], rtol=1e-4, atol=1e-6,
                                   err_msg=n)
        np.testing.assert_allclose(p_def[n], p_rs[n], rtol=1e-4, atol=1e-6,
                                   err_msg=n)
    # the column weight keeps its mp placement through the explicit dp step
    assert "mp" in str(st.params["0.weight"].sharding.spec)
    # slots live packed and dp-sharded (ZeRO-1 memory on the composed mesh)
    for name, sl in st.opt_state["slots"].items():
        for kk, arr in sl.items():
            assert arr.shape[0] == 2 and "dp" in str(arr.sharding.spec)


def test_grad_comm_composed_accumulation(devices8):
    p_def, _, _ = _comp_train({}, steps=6, k=2)
    p_rs, _, st = _comp_train({"FLAGS_grad_comm": "on",
                               "FLAGS_weight_update_sharding": True},
                              steps=6, k=2)
    assert isinstance(st._jitted, dict)
    for n in p_def:
        np.testing.assert_allclose(p_def[n], p_rs[n], rtol=1e-4, atol=1e-6,
                                   err_msg=n)


def test_grad_comm_composed_rejects_quantized_wire(devices8):
    _, _, st = _comp_train({"FLAGS_grad_comm": "on",
                            "FLAGS_allreduce_dtype": "bfloat16"})
    assert st._gc_cfg is None  # falls back to GSPMD with a warning


# ---------------------------------------------------------------------------
# satellites: split validation, ParallelCrossEntropy, mp_allreduce


def test_split_validates_and_annotates(devices8):
    from paddle_tpu.distributed.fleet import mp_layers as mpl
    mesh = dist_env.create_hybrid_mesh(mp=4)
    x = paddle.to_tensor(np.arange(32, dtype=np.float32).reshape(8, 4))
    with pytest.raises(ValueError):
        mpl.split(x, 3, axis=0)          # 8 % 3 != 0
    with pytest.raises(ValueError):
        mpl.split(x, 4, axis=2)          # bad axis
    with pytest.raises(TypeError):
        mpl.split(x, "four")
    with pytest.raises(ValueError):
        mpl.split(x, [2, 6], axis=0)     # unequal sections
    with pytest.raises(ValueError):
        mpl.split(x, [2, 2], axis=0)     # sections don't sum to dim
    out = mpl.split(x, 4, axis=0, group="mp")
    assert out.shape == x.shape          # logical tensor, annotated only
    with pytest.warns(UserWarning):
        mpl.split(x, 2, axis=0, group="mp")  # 2 != mesh mp size 4
    dist_env.set_mesh(None)
    assert mpl.split(x, 4, axis=0) is x  # no mesh: validated identity


def test_parallel_cross_entropy_matches_dense(devices8):
    from paddle_tpu.distributed.fleet.mp_layers import ParallelCrossEntropy
    from paddle_tpu.nn import functional as F
    dist_env.create_hybrid_mesh(mp=4)
    rng = np.random.default_rng(0)
    logits = paddle.to_tensor(rng.standard_normal((6, 16)).astype(np.float32))
    labels = paddle.to_tensor(np.array([1, 3, 0, 15, 7, 2], np.int64))
    ce = ParallelCrossEntropy(mp_group="mp")
    got = ce(logits, labels)
    want = F.cross_entropy(logits, labels, reduction="none")
    np.testing.assert_allclose(np.asarray(got.numpy()),
                               np.asarray(want.numpy()), rtol=1e-5)


def test_parallel_cross_entropy_on_dp_only_mesh(devices8):
    """A mesh without an 'mp' axis must not get a constraint naming one
    (trace-time ValueError); the seed supported dp-only meshes here."""
    from paddle_tpu.distributed.fleet.mp_layers import ParallelCrossEntropy
    from jax.sharding import Mesh
    dist_env.set_mesh(Mesh(np.array(jax.devices()), ("dp",)))
    rng = np.random.default_rng(2)
    logits = paddle.to_tensor(rng.standard_normal((4, 8)).astype(np.float32))
    labels = paddle.to_tensor(np.array([1, 0, 3, 7], np.int64))
    out = np.asarray(ParallelCrossEntropy()(logits, labels).numpy())
    assert out.shape == (4,) and np.isfinite(out).all()


def test_hybrid_step_does_not_mutate_shared_config(devices8):
    """HybridTrainStep records the head-major layout on a PRIVATE config
    copy — a shared config object (GPT_CONFIGS) handed to a later
    flags-off step must keep the logical layout."""
    paddle.set_flags(dict(_DEF))
    paddle.set_flags(SP)
    mesh = dist_env.create_hybrid_mesh(dp=2, mp=4)
    shared = _mini_cfg()
    opt = paddle.optimizer.AdamW(1e-3)
    step = HybridTrainStep(shared, opt, mesh=mesh, seed=0)
    assert step.config.qkv_head_major and not shared.qkv_head_major


def test_parallel_cross_entropy_ignore_index(devices8):
    from paddle_tpu.distributed.fleet.mp_layers import ParallelCrossEntropy
    from paddle_tpu.nn import functional as F
    dist_env.create_hybrid_mesh(mp=4)
    rng = np.random.default_rng(1)
    logits = paddle.to_tensor(rng.standard_normal((5, 8)).astype(np.float32))
    labels = paddle.to_tensor(np.array([1, -100, 3, -100, 0], np.int64))
    ce = ParallelCrossEntropy(ignore_index=-100)
    got = np.asarray(ce(logits, labels).numpy())
    want = np.asarray(F.cross_entropy(logits, labels, reduction="none",
                                      ignore_index=-100).numpy())
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert got[1] == 0.0 and got[3] == 0.0


def test_mp_allreduce_inside_shard_map(devices8):
    from paddle_tpu.distributed.fleet.mp_layers import mp_allreduce
    mesh = Mesh(np.array(jax.devices()[:4]), ("mp",))
    dist_env.set_mesh(mesh)

    def f(x):
        out = mp_allreduce(x)
        return out._data if hasattr(out, "_data") else out

    g = jax.jit(shard_map(f, mesh=mesh, in_specs=P("mp"), out_specs=P("mp")))
    x = np.arange(4, dtype=np.float32)
    np.testing.assert_allclose(np.asarray(g(x)), np.full(4, x.sum()))


def test_mp_allreduce_eager_identity(devices8):
    from paddle_tpu.distributed.fleet.mp_layers import mp_allreduce
    x = paddle.to_tensor([1.0, 2.0])
    out = mp_allreduce(x)
    np.testing.assert_allclose(np.asarray(out.numpy()), [1.0, 2.0])


# ---------------------------------------------------------------------------
# satellite: DataLoader prefetch_factor honored


def test_dataloader_prefetch_factor_one_honored():
    from paddle_tpu.io import DataLoader, TensorDataset
    ds = TensorDataset([paddle.to_tensor(np.arange(8, dtype=np.float32))])
    dl = DataLoader(ds, batch_size=2, num_workers=2, prefetch_factor=1)
    assert dl.prefetch_factor == 1
    assert len(list(dl)) == len(dl)


def test_dataloader_prefetch_factor_validated():
    from paddle_tpu.io import DataLoader, TensorDataset
    ds = TensorDataset([paddle.to_tensor(np.arange(8, dtype=np.float32))])
    with pytest.raises(ValueError):
        DataLoader(ds, batch_size=2, prefetch_factor=0)
    with pytest.raises(ValueError):
        DataLoader(ds, batch_size=2, prefetch_factor=1.5)
