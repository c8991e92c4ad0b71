"""chip_smoke.py on the CPU: the same leg bodies the chip runs, at toy width
with the Pallas kernels interpreted — so a PR that breaks the smoke learns
it in tier-1, not from a chip call. Plus the contracts that keep a failure
on the chip from hiding: no TPU -> non-zero exit, no MFU against a guessed
peak, one owner and one fixed place for the compile cache.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from paddle_tpu.models.gpt import GPTConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# hidden 128 so the quant GEMM widths (3H, 4H) are whole 128-lane blocks
CFG = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
                max_seq_len=128, compute_dtype="float32", use_flash=True,
                remat=True)


@pytest.fixture(scope="module")
def trained():
    leg, step, losses = chip_smoke.leg_train(
        CFG, chip_smoke.JaxEvents(), batch=2, seq=128, param_dtype=jnp.float32,
        moment_dtype="float32", expect_kernels=False, timed_steps=2)
    return leg, step, losses


def test_train_leg(trained):
    leg, step, losses = trained
    assert leg.ok, leg.line("train")
    assert len(losses) == 5
    # off the chip the flash kernel is NOT in the step: the check reads the
    # executable, not the config flag (use_flash is True in CFG)
    assert leg.info["mosaic_calls"] == 0


def test_serve_leg(trained):
    _, step, _ = trained
    leg, served = chip_smoke.leg_serve(
        CFG, step.params, prompt_lens=(5, 16, 16, 23, 40), max_new=6,
        expect_kernels=False)
    assert leg.ok, leg.line("serve")
    assert served["tok_off"].shape == (5, 6)
    # fp32 on the CPU: the README's bitwise contract holds outright
    assert leg.info["gen_identical_requests"] == 5
    assert leg.info["kernel_on_off_agreement"] == 1.0


def test_first_token_gate_is_not_vacuous(trained):
    """A token the reference ranks last fails the gate."""
    _, step, _ = trained
    ref = chip_smoke.reference_first_logits(CFG, step.params,
                                            [np.arange(1, 9)])
    leg = chip_smoke.Leg()

    class Wrong:
        tokens = [int(ref[0].argmin())]
    chip_smoke._check_first_tokens(leg, "bad", ref, [Wrong])
    assert not leg.ok


def test_kernels_leg():
    leg = chip_smoke.leg_kernels(CFG, batch=2, seq=128, num_slots=8,
                                 page_size=8, max_seq_len=128,
                                 interpret=True)
    assert leg.ok, leg.line("kernels")
    assert {"flash_fwd_bwd_d32", "flash_fwd_bwd_d16", "paged_decode",
            "paged_decode_q", "paged_decode_d20", "paged_decode_q_d20",
            "quant_gemm_F384", "quant_gemm_F512"} <= set(leg.info)


# ---------------------------------------------------------------------------
# what the CPU can say about the TPU build: jax.export lowers a program for
# platform "tpu" with no chip, through Pallas' lowering to Mosaic — the
# stage that refused the paged-decode kernel and the meshed trainer on the
# first chip run. (The Mosaic compiler itself only runs on the chip.)


def _lowers_for_tpu(fn, *args):
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *args).mlir_module().count(chip_smoke.MOSAIC_CALL)


def test_paged_and_quant_kernels_lower_for_tpu_at_the_1p3b_shapes():
    from paddle_tpu.ops.pallas_kernels.quant_gemm import quant_gemm_kernel
    from paddle_tpu.serving.paged_attention import (
        paged_decode_attention, paged_decode_attention_q)
    sds = jax.ShapeDtypeStruct
    B, ps, MP = 16, 16, 128                     # the GPT cells' decode
    P = B * MP + 1
    tab, pos = sds((B, MP), jnp.int32), sds((B,), jnp.int32)
    # 1.3B's heads, and 2.7B's: 32 of 80 in the pool's 128 lanes
    for nh, d in ((16, 128), (32, 80)):
        q = sds((B, nh, d), jnp.float32)
        for dt, scales in ((jnp.bfloat16, ()),
                           (jnp.int8, (sds((P,), jnp.float32),) * 2)):
            fn = paged_decode_attention_q if scales else \
                paged_decode_attention
            # one layer's pool, and the engine's call: a traced layer of
            # the whole stacked pool
            pool = sds((P, ps, nh, 128), dt)
            assert _lowers_for_tpu(
                lambda *a: fn(*a, page_size=ps), q, pool, pool, tab, pos,
                *scales) == 1
            pool = sds((24, P, ps, nh, 128), dt)
            assert _lowers_for_tpu(
                lambda l, *a: fn(*a, page_size=ps, layer=l),
                sds((), jnp.int32), q, pool, pool, tab, pos, *scales) == 1
    for F in (6144, 8192):
        assert _lowers_for_tpu(
            quant_gemm_kernel, sds((B, 2048), jnp.bfloat16),
            sds((2048, F), jnp.int8), sds((F,), jnp.float32)) == 1


@pytest.fixture
def mesh_dp2_mp2():
    from paddle_tpu.distributed import env as dist_env
    yield dist_env.create_hybrid_mesh(dp=2, mp=2)
    dist_env.set_mesh(None)


def _mesh_step(mesh, **cfg_kw):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt_hybrid import HybridTrainStep
    return HybridTrainStep(dataclasses.replace(CFG, **cfg_kw),
                           paddle.optimizer.AdamW(2e-4), mesh=mesh)


def _ids():
    return jax.random.randint(jax.random.key(1), (4, 128), 0, 512, jnp.int32)


def test_flash_runs_per_shard_under_a_gspmd_mesh(monkeypatch, mesh_dp2_mp2):
    """GSPMD cannot partition a Mosaic kernel: under a mesh the flash call
    is shard_map'ed over (dp: batch, mp: heads) and trains like the XLA
    attention on one device."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa
    ids = _ids()
    single = _mesh_step(None, use_flash=False)
    want = [float(single(ids)) for _ in range(3)]
    monkeypatch.setattr(fa, "flash_supported", lambda *a, **k: True)
    meshed = _mesh_step(mesh_dp2_mp2)
    got = [float(meshed(ids)) for _ in range(3)]
    np.testing.assert_allclose(got, want, atol=5e-6)


def test_meshed_trainer_with_mosaic_flash_lowers_for_tpu(monkeypatch,
                                                         mesh_dp2_mp2):
    """The chip's refusal of the dp2 x mp2 trainer ("Mosaic kernels cannot
    be automatically partitioned") was a lowering error: this lowering has
    to go through, with the kernel in it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = _mesh_step(mesh_dp2_mp2, compute_dtype="bfloat16")
    lowered = jax.export.export(step._build(), platforms=["tpu"])(
        step._flat(step.params), step.opt_state, _ids(),
        jnp.asarray(2e-4, jnp.float32))
    assert chip_smoke.MOSAIC_CALL in lowered.mlir_module()


def _run(script, env_extra=None, cwd=REPO):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_no_tpu_exits_nonzero_and_prints_no_result(script):
    p = _run([os.path.join(REPO, script)])
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert p.stdout.strip() == ""        # no LEG line, no JSON, no metric


def test_no_peak_for_unknown_device_and_no_mfu_off_tpu():
    from paddle_tpu.observability import default_peak_flops, peak_flops_bf16
    assert peak_flops_bf16("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="cpu"):
        peak_flops_bf16("cpu")
    assert default_peak_flops() is None


_CACHE_DIR = ("import paddle_tpu\n"
              "from paddle_tpu.framework import compilation_cache as cc\n"
              "cc.ensure_persistent_cache()\n"
              "print(cc.cache_dir())")


def test_cache_dir_is_the_variable_when_set(tmp_path):
    p = _run(["-c", _CACHE_DIR],
             {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "x"),
              "PYTHONPATH": REPO}, cwd=str(tmp_path))
    assert p.stdout.split()[-1] == str(tmp_path / "x"), p.stderr


def test_cache_dir_is_the_checkout_from_any_cwd(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    for cwd in (REPO, str(tmp_path)):
        p = _run(["-c", _CACHE_DIR], {"PYTHONPATH": REPO}, cwd=cwd)
        assert p.stdout.split()[-1] == want, p.stderr
