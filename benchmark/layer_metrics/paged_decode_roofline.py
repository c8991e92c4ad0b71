"""The paged decode kernel's share of its roofline: the bytes of K and V that
the live contexts hold, read once per decode step (plus q and o), over the
HBM bandwidth, against the kernel's summed duration in the traced part of
the window. Bytes bound it: one query row per slot does 2 FLOPs a byte."""
from benchmark.harness.trace import MOSAIC


def read(ctx):
    tr = ctx.trace
    if tr is None or not ctx.on_chip:
        return None
    dev = tr.used_devices()[0]
    kernel_s, n = tr.kernel_seconds(
        dev, lambda t: t.startswith("%paged_decode_attention") and MOSAIC in t)
    if n == 0 or kernel_s <= 0:
        return None
    p = ctx.facts["log"].processed(*ctx.traced)
    flops, nbytes = ctx.work.decode_attention_work(
        ctx.config, p["decode_ctx_positions"], p["decode_tokens"])
    least, _bound = ctx.work.roofline_seconds(flops, nbytes, ctx.peaks)
    return 100.0 * least / kernel_s
