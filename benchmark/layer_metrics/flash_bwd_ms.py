"""Device time a training step of the two flash backward kernels together:
the Mosaic operations named ``%flash_bwd_dq*`` and ``%flash_bwd_dkv*`` in
the traced steps, mean of the used devices, over the number of traced
steps."""
from benchmark.layer_metrics.flash_fwd_ms import step_ms


def read(ctx):
    return step_ms(ctx, ("%flash_bwd_dq", "%flash_bwd_dkv"))
