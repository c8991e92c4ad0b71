"""XLA backend compilations (jax.monitoring) between the first and the last
timed instant. A count: it should read 0."""


def read(ctx):
    return ctx.facts["compiles_in_window"]
