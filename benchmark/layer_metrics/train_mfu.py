"""Model FLOP/s utilisation of the whole training step: required FLOPs per
token (recomputed work not counted) x tokens/s over chips x peak."""


def read(ctx):
    if not ctx.on_chip:
        return None
    f = ctx.facts
    flops = ctx.work.train_flops_per_token(ctx.config, f["seq"]) * f["tokens"]
    return 100.0 * flops / ctx.work_window_s / (
        ctx.chips * ctx.peaks["flops_bf16"])
