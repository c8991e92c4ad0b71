"""Device time a training step of the flash forward kernel, its
recomputation under remat included: the Mosaic operations named
``%flash_fwd*`` (the program's ``jax.named_scope`` around the
``pallas_call``) in the traced steps, mean of the used devices, over the
number of traced steps."""
from benchmark.harness.trace import MOSAIC


def step_ms(ctx, prefixes):
    """Milliseconds a traced step of the Mosaic operations whose names start
    with one of ``prefixes``; None where there is no trace or no such
    operation (a program that does not name its kernels)."""
    tr = ctx.trace
    if tr is None or not ctx.on_chip:
        return None
    found = [tr.kernel_seconds(
        d, lambda t: MOSAIC in t and t.startswith(prefixes))
        for d in tr.used_devices()]
    steps = len(ctx.spans.durations("HybridTrainStep.__call__", *ctx.traced))
    if steps == 0 or not found or not any(n for _, n in found):
        return None
    return 1e3 * sum(s for s, _ in found) / len(found) / steps


def read(ctx):
    return step_ms(ctx, ("%flash_fwd",))
