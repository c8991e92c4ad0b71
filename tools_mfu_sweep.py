#!/usr/bin/env python
"""MFU sweep for ResNet-50 and BERT-base on the real chip (VERDICT r4 #4).

Runs a matrix of configs and prints one line per result. Every config is
independent and results stream as they finish.

  python tools_mfu_sweep.py resnet   # layout x dtype x batch sweep
  python tools_mfu_sweep.py bert     # seq/batch sweep with flash attn
  python tools_mfu_sweep.py flash    # pallas flash-attn tile sweep (GPT)
  python tools_mfu_sweep.py tp       # mp comm-schedule ladder, gpt3-1.3B
  python tools_mfu_sweep.py tp67 [B] # same ladder on gpt3-6.7B (ROADMAP
                                     # MFU rung; sweeps FLAGS_comm_backend
                                     # gspmd/ring/fused alongside the tp
                                     # flags)
  python tools_mfu_sweep.py pp [B]   # pipeline comm-backend ladder on a
                                     # dp x pp mesh (FLAGS_comm_backend=
                                     # 'pp=gspmd|ring|fused' + bf16 wire)
                                     # with a bubble-fraction column
"""
from __future__ import annotations

import sys
import time

import numpy as np


def _sync(x):
    import jax
    jax.device_get(jax.tree_util.tree_leaves(x)[0])


def _peak():
    # single-source FLOP/MFU estimators (paddle_tpu/observability/flops.py)
    # — shared with bench.py and the live step telemetry, so sweep numbers
    # and live MFU cannot diverge
    import jax
    from paddle_tpu.observability.flops import peak_flops_bf16
    return peak_flops_bf16(jax.devices()[0].device_kind)


def resnet_case(batch, data_format, dtype, steps=20):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn

    paddle.seed(0)
    model = paddle.vision.models.resnet50(num_classes=1000,
                                          data_format=data_format)
    opt = paddle.optimizer.Momentum(0.1, parameters=model.parameters())
    if dtype == "bf16":
        model, opt = paddle.amp.decorate(model, opt, level="O2",
                                         dtype="bfloat16")
    step = paddle.jit.TrainStep(model, nn.CrossEntropyLoss(), opt)
    import jax.numpy as jnp
    shape = (batch, 3, 224, 224) if data_format == "NCHW" \
        else (batch, 224, 224, 3)
    x_np = np.random.RandomState(0).rand(*shape).astype(np.float32)
    x = paddle.to_tensor(x_np)
    if dtype == "bf16":
        # activations must ENTER as bf16: conv casts weights UP to the
        # activation dtype, so fp32 input would silently run fp32 convs
        x = paddle.to_tensor(jnp.asarray(x_np, jnp.bfloat16))
    y = paddle.to_tensor(np.random.RandomState(1).randint(
        0, 1000, (batch, 1)).astype(np.int64))
    loss = step(x, y)          # compile
    _sync(loss._data)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x, y)
    _sync(loss._data)
    dt = (time.perf_counter() - t0) / steps
    img_s = batch / dt
    # ResNet-50 fwd ~4.1 GFLOPs/img @224; x3 for training
    mfu = img_s * 4.1e9 * 3 / _peak()
    print(f"RESNET50 {data_format} {dtype} bs{batch}: {img_s:.0f} img/s, "
          f"{dt * 1e3:.1f} ms/step, MFU {mfu * 100:.1f}%, "
          f"loss {float(np.asarray(loss.numpy())):.3f}", flush=True)


def bert_case(batch, seq, use_flash, steps=15, tiny=False):
    import paddle_tpu as paddle
    from paddle_tpu.models.bert import BertForPretraining, BertConfig

    cfg = BertConfig() if not tiny else BertConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128)
    # BertConfig has no use_flash field; the SDPA routing honors the
    # global flag (nn/functional/attention.py:105)
    paddle.set_flags({"FLAGS_use_flash_attention": use_flash})
    paddle.seed(0)
    net = BertForPretraining(cfg)
    opt = paddle.optimizer.AdamW(1e-4)
    net, opt = paddle.amp.decorate(net, opt, level="O2", dtype="bfloat16")
    # fused head+CE path: the [B, S, 30k] logits buffer of the plain
    # loss(forward()) OOMs the 16G chip at bs64 seq512

    class _Fused(paddle.nn.Layer):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def forward(self, ids, labels):
            return self.inner.pretraining_loss(ids, labels)

    step = paddle.jit.TrainStep(_Fused(net), lambda out: out, opt)
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int64))
    labels = paddle.to_tensor(np.random.RandomState(1).randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int64))
    loss = step((ids, labels), ())
    _sync(loss._data)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step((ids, labels), ())
    _sync(loss._data)
    dt = (time.perf_counter() - t0) / steps
    tok_s = batch * seq / dt
    from paddle_tpu.observability.flops import dense_flops_per_token
    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    mfu = tok_s * dense_flops_per_token(n_params) / _peak()
    print(f"BERT bs{batch} seq{seq} flash={use_flash}: "
          f"{tok_s:.0f} tok/s, {dt * 1e3:.1f} ms/step, "
          f"MFU {mfu * 100:.1f}%, loss "
          f"{float(np.asarray(loss.numpy())):.3f}", flush=True)


def gpt_flash_tiles(model_name="gpt3-1.3B", batch=8, seq=2048, steps=8):
    """Sweep pallas flash-attention tile sizes on the flagship config —
    the single-chip GPT MFU autotune surface (flash_block_q/k)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPT_CONFIGS
    from paddle_tpu.models.gpt_hybrid import HybridTrainStep

    for bq, bk in ((256, 256), (512, 256), (256, 512), (512, 512),
                   (1024, 256), (128, 128)):
        try:
            cfg = GPT_CONFIGS[model_name]
            cfg.max_seq_len = max(cfg.max_seq_len, seq)
            cfg.use_flash = True
            cfg.compute_dtype = "bfloat16"
            cfg.remat = True
            cfg.flash_block_q, cfg.flash_block_k = bq, bk
            opt = paddle.optimizer.AdamW(
                2e-4, grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0),
                moment_dtype="bfloat16")
            step = HybridTrainStep(cfg, opt, param_dtype=jnp.bfloat16)
            ids = jax.random.randint(jax.random.key(0), (batch, seq), 0,
                                     cfg.vocab_size, jnp.int32)
            loss = step(ids)
            _sync(loss)
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = step(ids)
            _sync(loss)
            dt = (time.perf_counter() - t0) / steps
            tok_s = batch * seq / dt
            from paddle_tpu.observability.flops import model_flops_per_token
            fpt, _ = model_flops_per_token(cfg, seq)
            print(f"FLASH {model_name} bq{bq} bk{bk}: {tok_s:.0f} tok/s, "
                  f"{dt:.3f} s/step, MFU {tok_s * fpt / _peak() * 100:.1f}%",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"FLASH bq{bq} bk{bk}: FAILED {str(e)[:140]}", flush=True)
        finally:
            import gc
            gc.collect()
            for a in jax.live_arrays():
                try:
                    a.delete()
                except Exception:  # noqa: BLE001
                    pass
            jax.clear_caches()


def gpt_tp_schedules(model_name="gpt3-1.3B", batch=8, seq=2048, steps=8,
                     mp=None):
    """Sweep the tensor-parallel schedule (FLAGS_sequence_parallel /
    FLAGS_mp_overlap / FLAGS_comm_backend) on a multi-chip mp mesh — the
    GSPMD-vs-explicit-vs-fused ladder of tools_tp_smoke.py at real-chip
    scale, reported as MFU. `tp67` runs it on the gpt3-6.7B config (the
    ROADMAP MFU rung: target >=45% at 6.7B)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.models.gpt import GPT_CONFIGS
    from paddle_tpu.models.gpt_hybrid import HybridTrainStep

    mp = mp or jax.device_count()
    ladder = (("gspmd", {}),
              ("seqpar", {"FLAGS_sequence_parallel": True}),
              ("seqpar+overlap", {"FLAGS_sequence_parallel": True,
                                  "FLAGS_mp_overlap": True}),
              ("ring-backend", {"FLAGS_comm_backend": "mp=ring"}),
              ("fused-backend", {"FLAGS_comm_backend": "mp=fused"}),
              ("fused-mp+ring-dp", {"FLAGS_comm_backend":
                                    "mp=fused,dp=ring"}))
    for name, flags in ladder:
        try:
            paddle.set_flags({"FLAGS_sequence_parallel": False,
                              "FLAGS_mp_overlap": False,
                              "FLAGS_comm_backend": ""})
            paddle.set_flags(flags)
            profiler.reset_mp_comm_counters()
            mesh = dist_env.create_hybrid_mesh(dp=-1, mp=mp)
            cfg = GPT_CONFIGS[model_name]
            cfg.max_seq_len = max(cfg.max_seq_len, seq)
            cfg.use_flash = True
            cfg.compute_dtype = "bfloat16"
            cfg.remat = True
            opt = paddle.optimizer.AdamW(
                2e-4, grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
            step = HybridTrainStep(cfg, opt, mesh=mesh,
                                   param_dtype=jnp.bfloat16)
            ids = jax.random.randint(jax.random.key(0), (batch, seq), 0,
                                     cfg.vocab_size, jnp.int32)
            loss = step(ids)
            _sync(loss)
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = step(ids)
            _sync(loss)
            dt = (time.perf_counter() - t0) / steps
            tok_s = batch * seq / dt
            from paddle_tpu.observability.flops import model_flops_per_token
            fpt, _ = model_flops_per_token(cfg, seq)
            peak = _peak() * jax.device_count()
            print(f"TP {model_name} mp{mp} {name}: {tok_s:.0f} tok/s, "
                  f"{dt:.3f} s/step, MFU {tok_s * fpt / peak * 100:.1f}%  "
                  f"[{profiler.mp_comm_summary()}]", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"TP {name}: FAILED {str(e)[:160]}", flush=True)
        finally:
            dist_env.set_mesh(None)


def gpt_pp_schedules(model_name="gpt3-1.3B", batch=8, seq=2048, steps=8,
                     pp=None, microbatches=8):
    """Sweep the pipeline-parallel comm backend (FLAGS_comm_backend=
    'pp=gspmd|ring|fused') on a dp x pp mesh — GSPMD's masked-select
    schedule vs the explicit overlapped ring schedule vs the fused
    last-GEMM RDMA boundary — reported as MFU plus the pp ledger's
    boundary traffic and bubble-fraction estimate per rung."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.models.gpt import GPT_CONFIGS
    from paddle_tpu.models.gpt_hybrid import HybridTrainStep

    pp = pp or min(4, jax.device_count())
    ladder = (("gspmd", {"FLAGS_comm_backend": ""}),
              ("ring", {"FLAGS_comm_backend": "pp=ring"}),
              ("ring+bf16-wire", {"FLAGS_comm_backend": "pp=ring",
                                  "FLAGS_pp_wire_dtype": "bfloat16"}),
              ("fused", {"FLAGS_comm_backend": "pp=fused"}))
    for name, flags in ladder:
        try:
            paddle.set_flags({"FLAGS_sequence_parallel": False,
                              "FLAGS_mp_overlap": False,
                              "FLAGS_comm_backend": "",
                              "FLAGS_pp_wire_dtype": "auto"})
            paddle.set_flags(flags)
            profiler.reset_pp_comm_counters()
            mesh = dist_env.create_hybrid_mesh(dp=-1, pp=pp)
            cfg = GPT_CONFIGS[model_name]
            cfg.max_seq_len = max(cfg.max_seq_len, seq)
            cfg.use_flash = True
            cfg.compute_dtype = "bfloat16"
            cfg.remat = True
            opt = paddle.optimizer.AdamW(
                2e-4, grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
            step = HybridTrainStep(cfg, opt, mesh=mesh,
                                   num_microbatches=microbatches,
                                   param_dtype=jnp.bfloat16)
            ids = jax.random.randint(jax.random.key(0), (batch, seq), 0,
                                     cfg.vocab_size, jnp.int32)
            loss = step(ids)
            _sync(loss)
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = step(ids)
            _sync(loss)
            dt = (time.perf_counter() - t0) / steps
            tok_s = batch * seq / dt
            from paddle_tpu.observability.flops import model_flops_per_token
            fpt, _ = model_flops_per_token(cfg, seq)
            peak = _peak() * jax.device_count()
            c = profiler.pp_comm_counters()
            per_step = max(c["steps"], 1)
            print(f"PP {model_name} pp{pp} M{microbatches} {name}: "
                  f"{tok_s:.0f} tok/s, {dt:.3f} s/step, "
                  f"MFU {tok_s * fpt / peak * 100:.1f}%  "
                  f"boundary {c['boundary_bytes'] / per_step / 1e6:.2f}MB  "
                  f"hops {c['ppermute_hops'] // per_step}  "
                  f"fused {c['fused_dispatches'] // per_step}  "
                  f"bubble {c['bubble_fraction'] * 100:.1f}%",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"PP {name}: FAILED {str(e)[:160]}", flush=True)
        finally:
            dist_env.set_mesh(None)


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "resnet"
    if which == "flash":
        gpt_flash_tiles()
        return
    if which == "tp":
        gpt_tp_schedules()
        return
    if which == "pp":
        # pipeline comm-backend ladder (PR 18): gspmd vs explicit ring
        # (plus bf16 partial-send wire) vs fused boundary, with the
        # ledger's bubble-fraction column; argv[2] overrides the batch
        batch = int(sys.argv[2]) if len(sys.argv) > 2 else 8
        gpt_pp_schedules(batch=batch)
        return
    if which == "tp67":
        # the ROADMAP 6.7B MFU rung: gspmd/ring/fused comm-backend ladder
        # on the flagship config (batch trimmed for the per-chip memory of
        # an mp-sharded 6.7B; bump with argv[2] on bigger slices)
        batch = int(sys.argv[2]) if len(sys.argv) > 2 else 4
        gpt_tp_schedules("gpt3-6.7B", batch=batch, seq=2048)
        return
    if which == "resnet":
        # big batches first: ~10-15 ms/step of the 62 ms bs128 step is RPC
        # arg marshaling (TPU_SMOKE round-5 breakdown), so bs512 amortizes
        for df in ("NHWC", "NCHW"):
            for dtype in ("bf16",):
                for bs in (512, 256, 128):
                    try:
                        resnet_case(bs, df, dtype)
                    except Exception as e:  # noqa: BLE001
                        print(f"RESNET50 {df} {dtype} bs{bs}: FAILED "
                              f"{str(e)[:160]}", flush=True)
    else:
        for bs, seq in ((64, 512), (128, 256), (32, 512)):
            for flash in (True, False):
                try:
                    bert_case(bs, seq, flash)
                except Exception as e:  # noqa: BLE001
                    print(f"BERT bs{bs} seq{seq} flash={flash}: FAILED "
                          f"{str(e)[:160]}", flush=True)


if __name__ == "__main__":
    main()
