#!/usr/bin/env python
"""GPT pretraining end-to-end — the flagship recipe.

Single chip:      python examples/gpt_pretrain.py
8-dev CPU mesh:   XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                  JAX_PLATFORMS=cpu python examples/gpt_pretrain.py --mesh

Covers: hybrid mesh, Strategy-configured Engine (amp/recompute/sharding),
checkpoint save + exact resume, and generation from the trained weights.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", action="store_true",
                    help="dp2 x mp2 x sharding2 mesh (8 devices)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--ckpt", default="/tmp/gpt_ckpt/model")
    args = ap.parse_args()

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed import Engine, Strategy, env
    from paddle_tpu.models.gpt import GPTConfig

    on_tpu = jax.default_backend() == "tpu"
    mesh = None
    if args.mesh:
        mesh = env.create_hybrid_mesh(dp=2, mp=2, pp=1, sharding=2, sp=1)

    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=4,
                    num_heads=4, max_seq_len=128,
                    compute_dtype="bfloat16" if on_tpu else "float32",
                    use_flash=on_tpu)

    strategy = Strategy({
        "recompute": {"enable": True},
        "sharding": {"enable": mesh is not None, "stage": 1,
                     "axis": "sharding"},
    })
    opt = paddle.optimizer.AdamW(
        3e-4, grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    engine = Engine(cfg, None, opt, strategy=strategy, mesh=mesh)

    rng = np.random.RandomState(0)
    def batch():
        return rng.randint(0, cfg.vocab_size, (8, 64)).astype(np.int64)

    print("training...")
    for step in range(args.steps):
        loss = float(np.asarray(jax.device_get(engine.run([batch()]))))
        if step % 5 == 0 or step == args.steps - 1:
            print(f"  step {step}: loss {loss:.4f}")

    engine.save(args.ckpt)
    print(f"checkpoint saved to {args.ckpt}.pdparams")

    # exact resume: a fresh engine restores and continues bit-identically
    import dataclasses
    engine2 = Engine(dataclasses.replace(cfg), None,
                     paddle.optimizer.AdamW(
                         3e-4,
                         grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0)),
                     strategy=strategy, mesh=mesh)
    engine2.load(args.ckpt)
    check = batch()
    ref = float(np.asarray(jax.device_get(
        engine._train_step.loss_only(check))))
    resumed = float(np.asarray(jax.device_get(
        engine2._train_step.loss_only(check))))
    assert abs(ref - resumed) < 1e-6, (ref, resumed)
    print(f"exact resume verified: loss_only {resumed:.4f} == {ref:.4f}")

    # generate from the trained weights (functional KV-cache decode)
    from paddle_tpu.models.generation import generate_from_params
    out = generate_from_params(engine._train_step.params,
                               np.array([[1, 2, 3, 4]], np.int32), cfg,
                               max_new_tokens=16, do_sample=True, top_k=5)
    print("generated token ids:", np.asarray(out.numpy())[0].tolist())


if __name__ == "__main__":
    main()
