#!/usr/bin/env python
"""Long-context evidence on the real chip: pallas flash attention fwd+bwd
at S=8k/16k/32k, single chip (the sp>1 ring path is validated on the
virtual mesh in dryrun_multichip; this measures the per-chip kernel the
ring schedule runs between ppermute steps).

Prints one line per config.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels.flash_attention import (
        flash_attention_bshd)

    assert jax.default_backend() == "tpu", jax.devices()
    H, D = 16, 64  # GPT-1.3B head geometry

    for S, B in ((8192, 4), (16384, 2), (32768, 1)):
        try:
            ks = jax.random.split(jax.random.key(0), 3)
            q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.bfloat16)
                       for kk in ks)

            def loss(q, k, v):
                return flash_attention_bshd(
                    q, k, v, causal=True).astype(jnp.float32).sum()

            g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
            val, grads = g(q, k, v)
            jax.device_get(val)
            steps = 5
            t0 = time.perf_counter()
            for _ in range(steps):
                val, grads = g(q, k, v)
            jax.device_get(val)
            dt = (time.perf_counter() - t0) / steps
            # causal attention FLOPs: fwd 2*2*B*H*S^2/2*D, bwd ~2.5x fwd
            fl = 3.5 * 2 * B * H * (S * S / 2) * D * 2
            print(f"FLASH-LONG S={S} B={B}: fwd+bwd {dt*1e3:.1f} ms, "
                  f"~{fl/dt/1e12:.1f} TF/s, peak-mem-free", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"FLASH-LONG S={S}: FAILED {str(e)[:200]}", flush=True)
        finally:
            import gc
            gc.collect()
            for a in jax.live_arrays():
                try:
                    a.delete()
                except Exception:  # noqa: BLE001
                    pass
            jax.clear_caches()


if __name__ == "__main__":
    main()
