"""The chunk kernel of the selective scan, ``ssm_scan``, against its
roofline: the least time the chip could take for the work it is required to
do (the family's ``ssm_scan_work``: the scan's FLOPs and operands at every
real position, the chunk's slot's float32 state read and written once a
call) over the summed duration of the ``%ssm_scan`` Mosaic calls in the
traced part of the window. The real positions are the program's counter
``ssm_scan_positions`` over the window, at the window's mean a chunk
dispatch; the traced dispatches are the calls over the model's Mamba
layers. The kernel walks its positions one after the other on the VPU, so
a bytes or FLOPs roofline reads low there: that low number is the honest
one. Nothing to read where no such call ran."""
from benchmark.layer_metrics import ssm_step_roofline as _step

KERNEL = "%ssm_scan"


def read(ctx):
    got = _step.kernel_calls(ctx, KERNEL)
    c = ctx.counters
    if got is None or not c.get("ssm_scan_positions") \
            or not c.get("chunk_steps"):
        return None
    seconds, calls = got
    traced = calls / ctx.work.ssm_layers(ctx.config)
    positions = c["ssm_scan_positions"] / c["chunk_steps"] * traced
    return _step.share(
        ctx, *ctx.work.ssm_scan_work(ctx.config, positions, calls), seconds)
