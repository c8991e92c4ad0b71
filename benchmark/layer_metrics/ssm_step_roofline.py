"""The decode kernel of the selective scan, ``ssm_step``, against its
roofline: the least time the chip could take for the work it is required to
do (the family's ``ssm_step_work``: a live slot's float32 state read and
written in every Mamba layer, its position's operands, the scan's FLOPs) over
the summed duration of the ``%ssm_step`` Mosaic calls in the traced part of
the window. The live slots are the program's counter ``ssm_step_slots`` over
the window, at the window's mean a decode dispatch; the traced dispatches are
the calls over the model's Mamba layers. Nothing to read where no such call
ran (a program without the kernel, a cell without the model)."""
from benchmark.harness.trace import MOSAIC

KERNEL = "%ssm_step"


def kernel_calls(ctx, name):
    """(seconds, calls) of the Mosaic calls named ``name`` in the traced
    window, or None."""
    tr = ctx.trace
    if tr is None or not ctx.on_chip or not tr.used_devices():
        return None
    seconds, n = tr.kernel_seconds(
        tr.used_devices()[0], lambda t: t.startswith(name) and MOSAIC in t)
    return (seconds, n) if n and seconds > 0 else None


def share(ctx, flops, nbytes, seconds):
    least, _bound = ctx.work.roofline_seconds(flops, nbytes, ctx.peaks)
    return 100.0 * least / seconds


def read(ctx):
    got = kernel_calls(ctx, KERNEL)
    c = ctx.counters
    dispatches = c.get("paged_steps", 0) - c.get("chunk_steps", 0)
    if got is None or not c.get("ssm_step_slots") or dispatches <= 0:
        return None
    seconds, calls = got
    traced = calls / ctx.work.ssm_layers(ctx.config)
    slot_steps = c["ssm_step_slots"] / dispatches * traced
    return share(ctx, *ctx.work.ssm_step_work(ctx.config, slot_steps, calls),
                 seconds)
