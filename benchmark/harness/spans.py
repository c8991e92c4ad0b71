"""The benchmark's own host spans, around its calls into the program. Each
span is kept in memory on the host's clock and, while the profiler runs, also
written into the profiler's trace (``TraceAnnotation``), so that an idle gap
of the device can be named by what the host was doing in it."""
import contextlib
import time

import jax


class Spans:
    def __init__(self):
        self.rows = []            # (name, start_s, end_s) on perf_counter
        self.annotate = False     # only while a trace is being taken

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        if self.annotate:
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.rows.append((name, t0, time.perf_counter()))

    def durations(self, name, t_from=None, t_to=None):
        return [e - s for n, s, e in self.rows if n == name
                and (t_from is None or s >= t_from)
                and (t_to is None or e <= t_to)]

    def names(self):
        return sorted({n for n, _, _ in self.rows})
