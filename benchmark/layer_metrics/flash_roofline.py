"""The flash attention kernels' share of their roofline in a training step:
the least time causal attention forward and backward could take at the
cell's true head_dim (the larger of FLOPs/peak and bytes/bandwidth, a chip's
share of it) over the summed duration of the Mosaic calls in the traced
steps. Every Mosaic call of the train step is a flash call today (forward,
its recomputation, and the two backward kernels); they carry no stable name
yet (see PERF.md, for the tracing issue)."""
from benchmark.harness.trace import MOSAIC


def read(ctx):
    tr = ctx.trace
    if tr is None or not ctx.on_chip:
        return None
    devs = tr.used_devices()
    secs = [tr.kernel_seconds(d, lambda text: MOSAIC in text)[0] for d in devs]
    kernel_s = sum(secs) / max(len(secs), 1)
    steps = len(ctx.spans.durations("HybridTrainStep.__call__", *ctx.traced))
    if kernel_s <= 0 or steps == 0:
        return None
    f = ctx.facts
    flops, nbytes = ctx.work.attention_train_work(ctx.config, f["batch"],
                                                  f["seq"])
    least, _bound = ctx.work.roofline_seconds(flops / ctx.chips,
                                              nbytes / ctx.chips, ctx.peaks)
    return 100.0 * least * steps / kernel_s
