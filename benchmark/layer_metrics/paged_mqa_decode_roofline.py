"""The paged decode kernel of one KV head, ``paged_mqa_decode``, against its
roofline: the bytes of K and V that the live contexts hold in the attention
layers, read once a decode step (plus q and o), over the HBM bandwidth,
against the summed duration of the ``%paged_mqa_decode`` Mosaic calls in the
traced part of the window. Nothing to read where no such call ran (a program
without the kernel, a cell without the model)."""
from benchmark.harness.trace import MOSAIC

KERNEL = "%paged_mqa_decode"


def read(ctx):
    tr = ctx.trace
    if tr is None or not ctx.on_chip or not tr.used_devices():
        return None
    work = getattr(ctx.work, "decode_attention_work", None)
    kernel_s, n = tr.kernel_seconds(
        tr.used_devices()[0], lambda t: t.startswith(KERNEL) and MOSAIC in t)
    if work is None or n == 0 or kernel_s <= 0:
        return None
    p = ctx.facts["log"].processed(*ctx.traced)
    if p["decode_tokens"] <= 0:
        return None
    flops, nbytes = work(ctx.config, p["decode_ctx_positions"],
                         p["decode_tokens"])
    least, _bound = ctx.work.roofline_seconds(flops, nbytes, ctx.peaks)
    return 100.0 * least / kernel_s
