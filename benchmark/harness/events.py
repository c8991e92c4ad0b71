"""Counts of jax.monitoring events, of which the XLA backend compilations
are read. One per process (a listener cannot be taken off again). After
chip_smoke.py's JaxEvents."""
import collections

import jax

COMPILE = "/jax/core/compile/backend_compile_duration"


class JaxEvents:
    def __init__(self):
        self.n = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda event, **kw: self.n.update([event]))
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, duration, **kw: self.n.update([event]))

    @property
    def compiles(self):
        return self.n[COMPILE]
