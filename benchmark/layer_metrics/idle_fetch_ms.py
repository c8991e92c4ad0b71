"""The device's idle time a paged dispatch that the host spent FETCHING its
outputs: the idle in the traced window that lies under a ``pt.serve.wait``
span that began before the gap did (the executable has ended and the host
does not hold its outputs yet), over the runs of the program's paged
executables whole inside the window (``idle_launch_ms.idle_split``)."""
from benchmark.layer_metrics.idle_launch_ms import idle_ms


def read(ctx):
    return idle_ms(ctx, "fetch")
