#!/usr/bin/env python
"""GPT-3-class pretraining throughput on one TPU chip.

One process walks the config ladder smallest first and prints ONE JSON line
for the configuration with the highest MFU:
  {"metric": ..., "value": tokens/sec/chip, "unit": "tokens/s/chip",
   "vs_baseline": mfu / 0.45, ...}
vs_baseline compares achieved MFU against the 45% target in BASELINE.json.

It measures the chip or it fails: no TPU, an unknown device kind, or the
first configuration that does not compile or run ends the process with a
non-zero exit code and no metric line. (Which configurations it runs and
what it prints are ROADMAP S1's to replace with a cell table.)
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time

from chip_smoke import free_device_memory
from paddle_tpu.observability.flops import (model_flops_per_token,
                                            peak_flops_bf16)

_T0 = time.time()

# (model, batch, seq), smallest first. 2.7B runs with host-offloaded moments
# (run() enables offload above 2e9 params).
LADDER = [("gpt3-345M", 8, 2048), ("gpt3-760M", 8, 2048),
          ("gpt3-1.3B", 8, 2048), ("gpt3-2.7B", 4, 2048)]


def _log(msg):
    sys.stderr.write(f"[bench +{time.time() - _T0:7.1f}s] {msg}\n")
    sys.stderr.flush()


def run(model_name, batch, seq, steps=10, warmup=2):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPT_CONFIGS
    from paddle_tpu.models.gpt_hybrid import HybridTrainStep

    base = GPT_CONFIGS[model_name]
    cfg = dataclasses.replace(base, max_seq_len=max(base.max_seq_len, seq),
                              use_flash=True, compute_dtype="bfloat16",
                              remat=True)

    # bf16 params; moments drop to bf16 storage when fp32 moments alone would
    # crowd a 16G chip (>= ~1B params: 2 + 8 bytes/param > half of HBM). The
    # measured alternative is a guaranteed compile-time HBM OOM ("Used 20.4G
    # of 15.75G") — bf16 moments are the single-chip analog of the
    # reference's ZeRO moment sharding across a GPU pod.
    fpt, n_params = model_flops_per_token(cfg, seq)
    moment_dtype = "bfloat16" if n_params > 1.0e9 else "float32"
    # 2.7B+: even bf16 moments + bf16 params exceed 16G HBM — stream the
    # moments from pinned host memory instead (fleet stage-3 offload analog)
    offload = n_params > 2.0e9
    opt = paddle.optimizer.AdamW(2e-4, grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0),
                                 moment_dtype=moment_dtype)
    _log(f"{model_name} bs={batch} seq={seq}: init params"
         f"{' (moments offloaded to host)' if offload else ''}...")
    step = HybridTrainStep(cfg, opt, param_dtype=jnp.bfloat16, offload=offload)
    ids = jax.random.randint(jax.random.key(0), (batch, seq), 0,
                             cfg.vocab_size, jnp.int32)

    _log("warmup (includes XLA compile)...")
    for _ in range(warmup):
        loss = step(ids)
    jax.block_until_ready(loss)
    _log("timed steps...")
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / steps
    tokens_per_sec = batch * seq / dt
    dev = jax.devices()[0]
    peak = peak_flops_bf16(dev.device_kind)
    mfu = tokens_per_sec * fpt / peak
    return {
        "metric": f"GPT pretrain tokens/sec/chip ({model_name}, seq={seq}, "
                  f"bs={batch}, bf16+remat+attn=pallas, 1 chip)",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "mfu": round(mfu, 4),
        "step_time_s": round(dt, 4),
        "loss": float(np.asarray(jax.device_get(loss))),
        "n_params": n_params,
        "attention": "pallas",
        "device": dev.device_kind,
        "backend": jax.default_backend(),
        "peak_flops_assumed": peak,
    }


def _better(a, b):
    return b is None or (a["mfu"], a["value"]) > (b["mfu"], b["value"])


def main():
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"bench.py measures a TPU chip; jax.default_backend() is "
                 f"{backend!r} — nothing measured")
    best = None
    for model_name, batch, seq in LADDER:
        result = run(model_name, batch, seq)
        _log(f"{result['metric']}: value={result['value']} "
             f"mfu={result['mfu']}")
        if _better(result, best):
            best = result
        free_device_memory()   # the next (bigger) model starts from empty HBM
    print(json.dumps(best))


if __name__ == "__main__":
    main()
