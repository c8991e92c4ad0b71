"""Mean device time of one chunk dispatch: as ``decode_device_ms`` for the
executables that the program's ``pt.serve.feed`` spans name with
``kind=chunk``, the mean over the chunk ladder's rungs as dispatched in the
traced window."""
from benchmark.layer_metrics.decode_device_ms import device_ms


def read(ctx):
    return device_ms(ctx, "chunk")
