"""The device's idle time a paged dispatch in the host loop's TURN between
dispatches: the idle in the traced window under neither a feed nor a wait
span (emit, admit, the boundary's remainder, between steps), over the runs
of the program's paged executables whole inside the window
(``idle_launch_ms.idle_split``)."""
from benchmark.layer_metrics.idle_launch_ms import idle_ms


def read(ctx):
    return idle_ms(ctx, "turn")
