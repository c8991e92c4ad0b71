"""Fused GEMM+collective Pallas kernels (ops/pallas_kernels/
fused_collectives.py) and the pluggable per-axis comm-schedule backend
(FLAGS_comm_backend, distributed/comm_backend.py), on the 8-virtual-device
CPU mesh in Pallas interpret mode:

  * kernel fwd+bwd parity BITWISE vs the unfused reference (the same
    schedule expressed with lax collectives that materialize every chunk
    buffer — fusion must remove the buffers, not change the math);
  * GPT-mini mp=4 20-step loss trajectory: backend=fused matches
    backend=ring and the gspmd baseline (fp32 tolerance);
  * counter gates: per-axis backend label, fused dispatch count matching
    the static schedule, zero ppermute hops under fused;
  * HLO gate: no full-size (seq, hidden) all-gather materialization and
    no ring ppermute hops in the fused compiled step;
  * grad_comm dp backend: fused bucket RS/AG kernels (bitwise vs their
    references), bf16 wire at 0.5x bytes, and the lifted dp x mp
    composed-mesh bf16 wire bail (int16 fixed-point, counter-verified);
  * resolve/bail fallback matrix with fix-naming messages.
"""
import re

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn, profiler
from paddle_tpu.distributed import env as dist_env
from paddle_tpu.distributed import comm_backend, grad_comm
from paddle_tpu.distributed import tp_overlap as tp
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.gpt_hybrid import (HybridTrainStep, init_gpt_params,
                                          gpt_hidden)
from paddle_tpu.ops.pallas_kernels import fused_collectives as fc

# the kernels and ring schedules under test return per-device values under
# replicated out_specs: replication checking off, as at their call sites
shard_map = functools.partial(jax.shard_map, check_vma=False)


_DEF = {
    "FLAGS_sequence_parallel": False,
    "FLAGS_mp_overlap": False,
    "FLAGS_comm_backend": "",
    "FLAGS_grad_comm": "auto",
    "FLAGS_weight_update_sharding": False,
    "FLAGS_allreduce_dtype": "float32",
}


@pytest.fixture(autouse=True)
def _reset(devices8):
    yield
    paddle.set_flags(dict(_DEF))
    dist_env.set_mesh(None)
    tp.reset_mp_counters()
    grad_comm.reset_comm_counters()
    fc.reset_trace_counts()


def _mp_mesh(n=4):
    return dist_env.create_single_axis_mesh("mp", n)


def _dp_mesh(n=8):
    return dist_env.create_single_axis_mesh("dp", n)


# ---------------------------------------------------------------------------
# FLAGS_comm_backend parsing


def test_comm_backend_parse():
    assert comm_backend.parse("") == {}
    assert comm_backend.parse("mp=fused") == {"mp": "fused"}
    assert comm_backend.parse("mp=fused,dp=ring") == {"mp": "fused",
                                                      "dp": "ring"}
    # a bare backend fans out to every scheduled axis (pp since PR 18)
    assert comm_backend.parse("ring") == {"dp": "ring", "mp": "ring",
                                          "pp": "ring"}
    assert comm_backend.parse({"mp": "gspmd"}) == {"mp": "gspmd"}
    # unknown backends are dropped (warn once), not fatal
    assert comm_backend.parse("mp=warp9") == {}
    assert comm_backend.parse("mp=fused,dp=warp9") == {"mp": "fused"}


def test_requested_reads_flag():
    paddle.set_flags({"FLAGS_comm_backend": "mp=fused,dp=ring"})
    assert comm_backend.requested("mp") == "fused"
    assert comm_backend.requested("dp") == "ring"
    assert comm_backend.requested("pp") is None


# ---------------------------------------------------------------------------
# kernel parity: BITWISE vs the unfused reference schedule


def _mk(mesh):
    return fc.meta_for(mesh, "mp", interpret=True)


def test_fused_ag_gemm_bitwise_vs_unfused_reference(devices8):
    n = 4
    mesh = _mp_mesh(n)
    meta = _mk(mesh)
    rng = np.random.RandomState(0)
    B, S, H, F = 2, 16, 8, 12
    xf = jnp.asarray(rng.randn(B, S, H).astype(np.float32))
    w = jnp.asarray(rng.randn(H, F).astype(np.float32))
    specs = dict(in_specs=(P(None, "mp", None), P(None, None)),
                 out_specs=P(None, None, None))
    fused = shard_map(lambda x, ww: fc.fused_ag_gemm(meta, x, ww),
                             mesh=mesh, **specs)
    ref = shard_map(lambda x, ww: fc.ag_gemm_reference("mp", n, x, ww),
                           mesh=mesh, **specs)
    got = jax.jit(fused)(xf, w)
    want = jax.jit(ref)(xf, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the schedule itself is exact vs the dense matmul here
    dense = jnp.einsum("bsh,hf->bsf", xf, w,
                       preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=1e-6, atol=1e-6)


def test_fused_gemm_rs_bitwise_vs_unfused_reference(devices8):
    n = 4
    mesh = _mp_mesh(n)
    meta = _mk(mesh)
    rng = np.random.RandomState(1)
    B, S, H, F = 2, 16, 8, 12
    yf = jnp.asarray(rng.randn(B, S, F).astype(np.float32))
    w = jnp.asarray(rng.randn(F, H).astype(np.float32))
    specs = dict(in_specs=(P(None, None, "mp"), P("mp", None)),
                 out_specs=P(None, "mp", None))
    fused = shard_map(lambda y, ww: fc.fused_gemm_rs(meta, y, ww),
                             mesh=mesh, **specs)
    ref = shard_map(lambda y, ww: fc.gemm_rs_reference("mp", n, y, ww),
                           mesh=mesh, **specs)
    got = jax.jit(fused)(yf, w)
    want = jax.jit(ref)(yf, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    dense = jnp.einsum("bsf,fh->bsh", yf, w,
                       preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)


def test_fused_vjp_bitwise_vs_unfused_schedule(devices8):
    """The custom-VJP backward kernels equal the unfused reference of the
    SAME backward schedule bitwise: dx of AG+GEMM is the cotangent's
    GEMM+RS, dw is the ring-gathered transpose accumulation."""
    n = 4
    mesh = _mp_mesh(n)
    meta = _mk(mesh)
    rng = np.random.RandomState(2)
    B, S, H, F = 2, 16, 8, 12
    xf = jnp.asarray(rng.randn(B, S, H).astype(np.float32))
    w = jnp.asarray(rng.randn(H, F).astype(np.float32))
    g = jnp.asarray(rng.randn(B, S, F).astype(np.float32))

    def fused_bwd(x, ww, gg):
        _, vjp = jax.vjp(lambda a, b: fc.fused_ag_gemm(meta, a, b), x, ww)
        return vjp(gg)

    def ref_bwd(x, ww, gg):
        dx = fc.gemm_rs_reference("mp", n, gg, ww.T)
        dw = fc.ag_accum_reference("mp", n, x, gg).astype(ww.dtype)
        return dx, dw

    specs = dict(
        in_specs=(P(None, "mp", None), P(None, None), P(None, None, None)),
        out_specs=(P(None, "mp", None), P(None, None)))
    got = jax.jit(shard_map(fused_bwd, mesh=mesh, **specs))(xf, w, g)
    want = jax.jit(shard_map(ref_bwd, mesh=mesh, **specs))(xf, w, g)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # end-to-end: grads of a column->gelu->row chain agree with the dense
    # model to fp32 tolerance

    def loss_fused(x, w1, w2):
        up = fc.fused_ag_gemm(meta, x, w1)
        local = jnp.sum(fc.fused_gemm_rs(meta, jax.nn.gelu(up), w2) ** 2)
        return lax.psum(local, "mp")    # seq-sharded output: global sum

    smap = shard_map(
        loss_fused, mesh=mesh,
        in_specs=(P(None, "mp", None), P(None, "mp"), P("mp", None)),
        out_specs=P())
    w1 = jnp.asarray(rng.randn(H, F).astype(np.float32) * 0.2)
    w2 = jnp.asarray(rng.randn(F, H).astype(np.float32) * 0.2)

    v1, g1 = jax.jit(jax.value_and_grad(
        lambda x, a, b: jnp.sum((jax.nn.gelu(x @ a) @ b) ** 2),
        argnums=(1, 2)))(xf, w1, w2)
    with mesh:
        v2, g2 = jax.jit(jax.value_and_grad(smap, argnums=(1, 2)))(
            xf, w1, w2)
    np.testing.assert_allclose(float(v1), float(v2), rtol=2e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-5)


def test_fused_rs_bucket_bitwise_incl_bf16_wire(devices8):
    n = 8
    mesh = _dp_mesh(n)
    meta = fc.meta_for(mesh, "dp", interpret=True)
    rng = np.random.RandomState(3)
    xall = jnp.asarray(rng.randn(n, n, 64).astype(np.float32))

    for wire in (None, jnp.bfloat16):
        fused = shard_map(
            lambda x: fc.fused_rs_bucket(meta, x, wire),
            mesh=mesh, in_specs=P("dp", None), out_specs=P("dp"))
        ref = shard_map(
            lambda x: fc.rs_bucket_reference("dp", n, x, wire),
            mesh=mesh, in_specs=P("dp", None), out_specs=P("dp"))
        got = jax.jit(fused)(xall.reshape(n * n, 64))
        want = jax.jit(ref)(xall.reshape(n * n, 64))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # fp32 wire is exact vs the sum; bf16 wire within quantization noise
    exact = np.asarray(xall).sum(axis=0).reshape(-1)
    np.testing.assert_allclose(np.asarray(got), exact, rtol=0.1, atol=0.2)


def test_fused_ag_bucket_matches_all_gather(devices8):
    n = 8
    mesh = _dp_mesh(n)
    meta = fc.meta_for(mesh, "dp", interpret=True)
    rng = np.random.RandomState(4)
    rows = jnp.asarray(rng.randn(n, 32).astype(np.float32))
    fused = shard_map(
        lambda r: fc.fused_ag_bucket(meta, r[0]),
        mesh=mesh, in_specs=P("dp", None), out_specs=P(None, None))
    ref = shard_map(
        lambda r: lax.all_gather(r[0], "dp", tiled=False),
        mesh=mesh, in_specs=P("dp", None), out_specs=P(None, None))
    got = jax.jit(fused)(rows)
    want = jax.jit(ref)(rows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# GPT-mini mp=4: gspmd / ring / fused ladder (the acceptance trajectory)


def _mini_cfg():
    return GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=64, compute_dtype="float32",
                     use_flash=False, remat=True, dropout=0.0)


def _gpt_run(flags, steps=20, mp=4, batch=8, seq=32):
    paddle.set_flags(dict(_DEF))
    paddle.set_flags(flags)
    tp.reset_mp_counters()
    mesh = _mp_mesh(mp)
    cfg = _mini_cfg()
    opt = paddle.optimizer.AdamW(1e-3)
    step = HybridTrainStep(cfg, opt, mesh=mesh, seed=0)
    ids = jax.random.randint(jax.random.key(0), (batch, seq), 0,
                             cfg.vocab_size, jnp.int32)
    losses = [float(step(ids)) for _ in range(steps)]
    counters = tp.mp_counters()
    dist_env.set_mesh(None)
    return losses, counters


def test_fused_matches_ring_and_gspmd_20_steps(devices8):
    base, cb = _gpt_run({})
    ring, cr = _gpt_run({"FLAGS_comm_backend": "mp=ring"})
    fused, cf = _gpt_run({"FLAGS_comm_backend": "mp=fused"})
    np.testing.assert_allclose(base, ring, rtol=5e-4, atol=1e-5)
    np.testing.assert_allclose(base, fused, rtol=5e-4, atol=1e-5)
    np.testing.assert_allclose(ring, fused, rtol=5e-4, atol=1e-5)
    # counter gates: backend label + fused dispatch count == the static
    # schedule (4 kernel positions per block per step), zero ppermute hops
    assert cb["steps"] == 0
    assert cr["backend"] == {"mp": "ring"} and cr["ppermute_hops"] > 0
    assert cf["backend"] == {"mp": "fused"}
    assert cf["ppermute_hops"] == 0
    L = 2
    assert cf["fused_dispatches"] == 20 * 4 * L
    assert cr["fused_dispatches"] == 0
    # same wire bytes either way (the decomposition changes, the bytes
    # don't)
    assert cf["rs_bytes"] == cr["rs_bytes"] > 0
    assert cf["ag_bytes"] == cr["ag_bytes"] > 0


def test_mp_comm_summary_names_backend(devices8):
    _gpt_run({"FLAGS_comm_backend": "mp=fused"}, steps=1)
    s = profiler.mp_comm_summary()
    assert "backend: mp=fused" in s and "fused-dispatches: 8" in s


def test_flags_off_trajectory_bitwise_after_fused_run(devices8):
    """Running the fused backend must not perturb a fresh flags-off
    trajectory (same seed, same data): the default program stays
    byte-identical to the seed."""
    def run_off():
        paddle.set_flags(dict(_DEF))
        mesh = dist_env.create_hybrid_mesh(dp=2, mp=4)
        cfg = _mini_cfg()
        opt = paddle.optimizer.AdamW(1e-3)
        step = HybridTrainStep(cfg, opt, mesh=mesh, seed=0)
        ids = jax.random.randint(jax.random.key(0), (8, 32), 0,
                                 cfg.vocab_size, jnp.int32)
        for _ in range(3):
            step(ids)
        params = jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(a)), step.params)
        dist_env.set_mesh(None)
        return params

    p1 = run_off()
    _gpt_run({"FLAGS_comm_backend": "mp=fused"}, steps=1)
    p2 = run_off()
    jax.tree_util.tree_map(np.testing.assert_array_equal, p1, p2)


# ---------------------------------------------------------------------------
# HLO + trace gates: the structural proof the fusion happened


def _lowered_text(flags, mesh):
    paddle.set_flags(dict(_DEF))
    paddle.set_flags(flags)
    cfg = _mini_cfg()
    params = init_gpt_params(cfg, jax.random.key(0))
    if tp.mp_backend_requested():
        params["blocks"] = tp.to_qkv_head_major(
            params["blocks"], cfg.hidden_size, cfg.num_heads)
        cfg.qkv_head_major = True
    fn = jax.jit(lambda p, i: gpt_hidden(p, i, cfg, mesh))
    return fn.lower(params, jnp.zeros((8, 32), jnp.int32)).compile().as_text()


def test_hlo_gate_no_full_size_ag_and_no_ppermute_under_fused(devices8):
    mesh = _mp_mesh(4)
    sp = _lowered_text({"FLAGS_sequence_parallel": True}, mesh)
    ring = _lowered_text({"FLAGS_comm_backend": "mp=ring"}, mesh)
    fused = _lowered_text({"FLAGS_comm_backend": "mp=fused"}, mesh)

    def full_ag(txt):
        # an all-gather materializing a full-sequence activation
        # (f32[batch, seq, ...] with seq=32)
        return len(re.findall(r"all-gather[^\n]*f32\[8,32,", txt))

    def cp(txt):
        return len(re.findall(r"collective-permute", txt))

    # the plain RS/AG schedule materializes the gathered [B,S,*] operand
    assert full_ag(sp) > 0
    # ring removes the buffer by decomposing into ppermute hops
    assert full_ag(ring) == 0 and cp(ring) > cp(sp)
    # fused removes BOTH: no full-size gather, and the block schedule adds
    # zero ppermute hops over the non-block baseline (the remaining CPs
    # are the embedding-entry reduce-scatter emulation shared with `sp`;
    # chunk-sized all-gathers in the text are the CPU interpret-mode
    # emulation of the in-kernel remote DMA, none of them full-size)
    assert full_ag(fused) == 0
    assert cp(fused) == cp(sp)


def test_fused_kernel_trace_counts(devices8):
    """A forward trace dispatches exactly the static kernel positions:
    2 AG+GEMM (qkv, up) + 2 GEMM+RS (attn out, down) per scan body."""
    mesh = _mp_mesh(4)
    paddle.set_flags(dict(_DEF))
    paddle.set_flags({"FLAGS_comm_backend": "mp=fused"})
    cfg = _mini_cfg()
    params = init_gpt_params(cfg, jax.random.key(0))
    params["blocks"] = tp.to_qkv_head_major(
        params["blocks"], cfg.hidden_size, cfg.num_heads)
    cfg.qkv_head_major = True
    fc.reset_trace_counts()
    jax.jit(lambda p, i: gpt_hidden(p, i, cfg, mesh)).lower(
        params, jnp.zeros((8, 32), jnp.int32))
    counts = fc.trace_counts()
    assert counts == {"ag_gemm": 2, "gemm_rs": 2}
    dist_env.set_mesh(None)


# ---------------------------------------------------------------------------
# resolve / fallback matrix


def test_resolve_backend_matrix(devices8):
    cfg = _mini_cfg()
    cfg.qkv_head_major = True
    mesh1 = _mp_mesh(4)
    paddle.set_flags(dict(_DEF))
    assert tp.resolve_gpt(cfg, mesh1) is None                # flags off
    # mp=ring implies the sequence-parallel layout (no second flag needed)
    paddle.set_flags({"FLAGS_comm_backend": "mp=ring"})
    got = tp.resolve_gpt(cfg, mesh1, batch=8, seq=32)
    assert got is not None and got.backend == "ring" and got.overlap
    paddle.set_flags({"FLAGS_comm_backend": "mp=fused"})
    got = tp.resolve_gpt(cfg, mesh1, batch=8, seq=32)
    assert got.backend == "fused" and not got.overlap
    assert got.batch_axis is None                            # mp-only mesh
    # mp=gspmd forces the partitioner schedule even with sp flags on
    paddle.set_flags({"FLAGS_comm_backend": "mp=gspmd"})
    assert tp.resolve_gpt(cfg, mesh1, batch=8, seq=32) is None
    paddle.set_flags({"FLAGS_comm_backend": "mp=gspmd",
                      "FLAGS_sequence_parallel": True})
    got = tp.resolve_gpt(cfg, mesh1, batch=8, seq=32)
    assert got is not None and got.backend == "rsag"
    dist_env.set_mesh(None)
    # fused on a multi-axis mesh falls back to ring on CPU (interpret-mode
    # remote DMA needs a single named axis)
    mesh6 = dist_env.create_hybrid_mesh(dp=2, mp=4)
    paddle.set_flags({"FLAGS_comm_backend": "mp=fused",
                      "FLAGS_sequence_parallel": False})
    got = tp.resolve_gpt(cfg, mesh6, batch=8, seq=32)
    assert got is not None and got.backend == "ring"
    assert tp.layer_schedule(mesh6) == "explicit"
    dist_env.set_mesh(None)


def test_layer_schedule_fused_mode(devices8):
    mesh = _mp_mesh(4)
    paddle.set_flags(dict(_DEF))
    assert tp.layer_schedule(mesh) == "gspmd"
    paddle.set_flags({"FLAGS_comm_backend": "mp=fused"})
    assert tp.layer_schedule(mesh) == "fused"
    paddle.set_flags({"FLAGS_comm_backend": "mp=gspmd",
                      "FLAGS_sequence_parallel": True})
    assert tp.layer_schedule(mesh) == "seq"


def test_mp_layers_fused_parity(devices8):
    """Column/RowParallelLinear route through the fused kernels on a
    single-axis mp mesh and match the GSPMD baseline."""
    def losses(flags):
        paddle.set_flags(dict(_DEF))
        paddle.set_flags(flags)
        mesh = _mp_mesh(4)
        paddle.seed(11)
        from paddle_tpu.distributed.fleet.mp_layers import (
            ColumnParallelLinear, RowParallelLinear)
        m = nn.Sequential(
            ColumnParallelLinear(32, 64, gather_output=False),
            nn.GELU(),
            RowParallelLinear(64, 32, input_is_parallel=True))
        opt = paddle.optimizer.AdamW(0.01, parameters=m.parameters())
        step = paddle.jit.TrainStep(m, nn.MSELoss(), opt, mesh=mesh)
        rng = np.random.default_rng(0)
        x = rng.standard_normal((4, 8, 32)).astype(np.float32)
        y = rng.standard_normal((4, 8, 32)).astype(np.float32)
        out = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)).numpy())
               for _ in range(3)]
        dist_env.set_mesh(None)
        return out

    base = losses({})
    fused = losses({"FLAGS_comm_backend": "mp=fused"})
    np.testing.assert_allclose(base, fused, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# grad_comm dp backend: fused kernels + quantized wire


def _dp_model():
    paddle.seed(3)
    return nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))


def _dp_train(flags, steps=4):
    paddle.set_flags(dict(_DEF))
    paddle.set_flags(flags)
    profiler.reset_comm_counters()
    mesh = _dp_mesh(8)
    m = _dp_model()
    opt = paddle.optimizer.AdamW(0.01, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, nn.MSELoss(), opt, mesh=mesh)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 16)).astype(np.float32)
    y = rng.standard_normal((8, 8)).astype(np.float32)
    losses = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)).numpy())
              for _ in range(steps)]
    p = {n_: np.asarray(a) for n_, a in step.params.items()}
    c = profiler.comm_counters()
    cfg = step._gc_cfg
    dist_env.set_mesh(None)
    return p, losses, c, cfg


def test_grad_comm_fused_backend_parity(devices8):
    p0, _, _, cfg0 = _dp_train({})
    assert cfg0 is None
    p1, _, c1, cfg1 = _dp_train({"FLAGS_comm_backend": "dp=ring"})
    assert cfg1.backend == "ring" and not cfg1.fused_kernels
    assert c1["backend"] == {"dp": "ring"} and c1["fused_dispatches"] == 0
    p2, _, c2, cfg2 = _dp_train({"FLAGS_comm_backend": "dp=fused"})
    assert cfg2.backend == "fused" and cfg2.fused_kernels
    assert c2["backend"] == {"dp": "fused"}
    # static schedule: RS + grad-AG kernel per float bucket per step
    assert c2["fused_dispatches"] == c2["steps"] * 2 * (c2["buckets"]
                                                        // c2["steps"])
    p3, _, c3, cfg3 = _dp_train({"FLAGS_comm_backend": "dp=fused",
                                 "FLAGS_weight_update_sharding": True})
    assert cfg3.fused_kernels and cfg3.weight_update_sharding
    for n_ in p0:
        np.testing.assert_allclose(p0[n_], p1[n_], rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(p0[n_], p2[n_], rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(p0[n_], p3[n_], rtol=1e-4, atol=1e-6)


def test_grad_comm_fused_bf16_wire_halves_bytes(devices8):
    p0, l0, c0, _ = _dp_train({"FLAGS_comm_backend": "dp=fused",
                               "FLAGS_weight_update_sharding": True})
    pq, lq, cq, cfgq = _dp_train({"FLAGS_comm_backend": "dp=fused",
                                  "FLAGS_weight_update_sharding": True,
                                  "FLAGS_allreduce_dtype": "bfloat16"})
    assert cfgq.fused_kernels and cfgq.wire_dtype is jnp.bfloat16
    # counter-verified: the bf16 wire moves exactly half the fp32 bytes
    rs_fp32 = c0["reduce_bytes_by_dtype"]["float32"]
    rs_bf16 = cq["reduce_bytes_by_dtype"]["bfloat16"]
    assert rs_bf16 * 2 == rs_fp32
    for n_ in p0:
        np.testing.assert_allclose(p0[n_], pq[n_], rtol=2e-2, atol=1e-3)
    assert lq[-1] < lq[0]  # loss sanity: still trains


# ---------------------------------------------------------------------------
# the lifted dp x mp composed bf16 wire (mp-wire bail)


def _comp_model():
    paddle.seed(7)
    from paddle_tpu.distributed.fleet.mp_layers import (
        ColumnParallelLinear, RowParallelLinear)
    return nn.Sequential(
        ColumnParallelLinear(16, 32, gather_output=False),
        nn.ReLU(),
        RowParallelLinear(32, 16, input_is_parallel=True),
        nn.Linear(16, 8))


def _comp_train(flags, steps=6):
    paddle.set_flags(dict(_DEF))
    paddle.set_flags(flags)
    profiler.reset_comm_counters()
    mesh = dist_env.create_hybrid_mesh(dp=2, mp=4)
    m = _comp_model()
    opt = paddle.optimizer.AdamW(0.01, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, nn.MSELoss(), opt, mesh=mesh)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 16)).astype(np.float32)
    y = rng.standard_normal((8, 8)).astype(np.float32)
    losses = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)).numpy())
              for _ in range(steps)]
    p = {n_: np.asarray(a) for n_, a in step.params.items()}
    c = profiler.comm_counters()
    cfg = step._gc_cfg
    dist_env.set_mesh(None)
    return p, losses, c, cfg


def test_composed_bf16_wire_no_longer_bails(devices8):
    p0, _, c0, cfg0 = _comp_train({"FLAGS_grad_comm": "on"})
    assert cfg0 is not None and cfg0.auto_axes == ("mp",)
    pq, lq, cq, cfgq = _comp_train({"FLAGS_grad_comm": "on",
                                    "FLAGS_comm_backend": "dp=fused",
                                    "FLAGS_allreduce_dtype": "bfloat16"})
    # the ("mp-wire", ...) bail is lifted: the explicit schedule runs with
    # the int16 fixed-point realization of the bf16-width wire
    assert cfgq is not None and cfgq.backend == "fused" and cfgq.fixed16
    assert not cfgq.fused_kernels       # kernels can't partition there
    # counter-verified 0.5x: the int16 scatter moves exactly half the fp32
    # bytes the same RS would have moved (reconstructed from the static
    # plan; the fp32 key carries the unchanged gather side + scale psums)
    assert cfgq.plan is not None
    n = cfgq.n
    frac = (n - 1) / n
    from paddle_tpu.distributed.grad_comm import _int8_chunking
    rs_fp32 = sum(int(b.cols * n * 4 * frac) for b in cfgq.plan.buckets)
    rs_int16 = sum(int(_int8_chunking(b.cols)[2] * n * 2 * frac)
                   for b in cfgq.plan.buckets)
    assert cq["reduce_bytes_by_dtype"]["int16"] == cq["steps"] * rs_int16
    # 0.5x modulo the per-bucket chunk padding
    pad_slack = sum(int((_int8_chunking(b.cols)[2] - b.cols) * n * 2 * frac)
                    for b in cfgq.plan.buckets)
    assert rs_fp32 <= 2 * rs_int16 <= rs_fp32 + 2 * pad_slack + 1
    # parity within quantization tolerance + loss sanity
    for n_ in p0:
        np.testing.assert_allclose(p0[n_], pq[n_], rtol=2e-2, atol=1e-3,
                                   err_msg=n_)
    assert lq[-1] < lq[0]
    # legacy ring backend still bails (with the fix named in the warning)
    _, _, _, cfg2 = _comp_train({"FLAGS_grad_comm": "on",
                                 "FLAGS_allreduce_dtype": "bfloat16"})
    assert cfg2 is None
    # int8 + composed still bails even under fused
    _, _, _, cfg3 = _comp_train({"FLAGS_grad_comm": "on",
                                 "FLAGS_comm_backend": "dp=fused",
                                 "FLAGS_allreduce_dtype": "int8"})
    assert cfg3 is None


def test_dp_gspmd_backend_forces_default(devices8):
    _, _, _, cfg = _dp_train({"FLAGS_comm_backend": "dp=gspmd",
                              "FLAGS_weight_update_sharding": True})
    assert cfg is None
