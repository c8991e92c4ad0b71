#!/usr/bin/env python
"""Stage-3 full-offload smoke on the real chip: 6.7B (and 13B stretch)
GPT training on a single 16 GB chip backed by host RAM.

  python tools_stage3_smoke.py 6.7B [stream|host]
  python tools_stage3_smoke.py 13B  [stream|host]
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main():
    model = sys.argv[1] if len(sys.argv) > 1 else "6.7B"
    update = sys.argv[2] if len(sys.argv) > 2 else "stream"
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPT_CONFIGS
    from paddle_tpu.models.gpt_stage3_offload import Stage3OffloadTrainStep
    from paddle_tpu.observability.flops import (model_flops_per_token,
                                                peak_flops_bf16)

    assert jax.default_backend() == "tpu", jax.devices()
    if model == "tiny":
        # cheap probe for the offload machinery (esp. the compute_on
        # host-update branch) before burning time on a 6.7B attempt
        from paddle_tpu.models.gpt import GPTConfig
        GPT_CONFIGS["gpt3-tiny"] = GPTConfig(
            vocab_size=1024, hidden_size=256, num_layers=4, num_heads=4,
            max_seq_len=256)
    name = f"gpt3-{model}"
    cfg = GPT_CONFIGS[name]
    batch, seq = (1, 2048) if model == "13B" else \
        (2, 256) if model == "tiny" else (2, 2048)
    cfg.max_seq_len = max(cfg.max_seq_len, seq)
    cfg.use_flash = True
    cfg.compute_dtype = "bfloat16"
    opt = paddle.optimizer.AdamW(1e-4, moment_dtype="bfloat16")
    t0 = time.time()
    print(f"{name} bs={batch} seq={seq} update={update}: init "
          f"(host-resident params)...", flush=True)
    step = Stage3OffloadTrainStep(cfg, opt, param_dtype=jnp.bfloat16,
                                  update=update)
    n = step.num_params()
    print(f"  {n/1e9:.2f}B params resident on host "
          f"(+{time.time()-t0:.0f}s)", flush=True)
    ids = jax.random.randint(jax.random.key(0), (batch, seq), 0,
                             cfg.vocab_size, jnp.int32)
    loss = step(ids)
    print(f"  compile+step0 done loss={float(jax.device_get(loss)):.4f} "
          f"(+{time.time()-t0:.0f}s)", flush=True)
    steps = 3
    t1 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids)
    jax.device_get(loss)
    dt = (time.perf_counter() - t1) / steps
    tok_s = batch * seq / dt
    fpt, _ = model_flops_per_token(cfg, seq)
    peak = peak_flops_bf16(jax.devices()[0].device_kind)
    print(f"STAGE3 {name} bs={batch} seq={seq} update={update}: "
          f"{tok_s:.1f} tok/s, {dt:.2f} s/step, "
          f"MFU {tok_s*fpt/peak*100:.1f}%, "
          f"loss {float(jax.device_get(loss)):.4f}", flush=True)


if __name__ == "__main__":
    main()
