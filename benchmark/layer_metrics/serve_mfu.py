"""Model FLOP/s utilisation of serving: forward FLOPs of every prompt and
output token processed in the window over window x peak."""


def read(ctx):
    if not ctx.on_chip:
        return None
    log = ctx.facts["log"]
    p = log.processed(log.t0, log.t_close)
    flops = ctx.work.serve_flops(ctx.config, p["ctx_positions"], p["tokens"])
    if flops == 0:
        return None
    return 100.0 * flops / ctx.work_window_s / (
        ctx.chips * ctx.peaks["flops_bf16"])
