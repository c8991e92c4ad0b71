"""Planted faults of the jamba family, for the readings that the cell's limit
is set from (``tools/serve_readings.py``): each is a context in which the
family's plain reference computes a model that is wrong in one way, so that
put in the program's place it has to come out not correct. Not part of the
family's interface and never used by a run of the benchmark."""
import contextlib

import jax.numpy as jnp

from . import reference as ref


@contextlib.contextmanager
def _patched(module, **new):
    old = {name: getattr(module, name) for name in new}
    for name, fn in new.items():
        setattr(module, name, fn)
    try:
        yield
    finally:
        for name, fn in old.items():
            setattr(module, name, fn)


def state_not_zeroed():
    """A Mamba layer that starts a sequence from what its slot's last
    occupant left: the recurrent state the row itself ends in (a state as
    large as any) and its convolution input's last rows, not zeros."""
    return _patched(ref, scan_start=lambda recur, shape: recur(
        jnp.zeros(shape, jnp.float32))[1],
        before_sequence=lambda xi, rows: xi[-rows:])


def pads_advance_state(page_size=16):
    """A padded chunk that advances the state: past the chunk's last real
    position its pad lanes decay the state once more each. A chunk may end
    at any position that is no multiple of the page; planted before the
    first position of every page but the first, one pad's decay (at that
    position's own ``dt``, with no input), so that a request of any length
    meets it."""
    plain = ref.advance

    def wrong(s, dA, dBx, t):
        first = (t % page_size == 0) & (t > 0)
        return plain(jnp.where(first, dA * s, s), dA, dBx, t)

    return _patched(ref, advance=wrong)


def dt_bc_norms_dropped():
    """dt, B and C go on without their RMS norms."""
    return _patched(ref, ssm_norm=lambda x, eps, g: x)


def taps_reversed():
    """The taps applied newest row first."""
    return _patched(ref, taps_of=lambda w: w[::-1])


def state_in_bfloat16():
    """The recurrent state stored in bfloat16 between positions."""
    plain = ref.advance
    return _patched(ref, advance=lambda s, dA, dBx, t: plain(
        s, dA, dBx, t).astype(jnp.bfloat16).astype(jnp.float32))


def rotary_applied(theta=10000.0):
    """q and k rotated by position (the half-split pairing over the whole
    head, ``theta`` 1e4), where the family has no positional encoding."""
    def rotate(x):
        T, _, d = x.shape
        half = d // 2
        inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                                b * jnp.cos(ang) + a * jnp.sin(ang)], -1)

    return _patched(ref, position_code=lambda q, k: (rotate(q), rotate(k)))


FAULTS = {"pads_advance_state": pads_advance_state,
          "dt_bc_norms_dropped": dt_bc_norms_dropped,
          "taps_reversed": taps_reversed}

# Faults that the served tokens of the jamba cell do not resolve: read on the
# chip through the cell's own check, the checked request's widest logit gap
# was 0.023-0.289 with the state not zeroed, 0.000 with it in bfloat16 and
# 0.066-0.088 with rotary, where the program itself reads up to 0.22 and the
# limit is 0.6. The CPU tests catch each where it is made: a reused slot
# starts from zero and the state stays float32 (tests/test_jamba_serving.py),
# and the forward matches the reference's logits, which carry no rotary, to
# 4e-5.
UNRESOLVED = {"state_not_zeroed": state_not_zeroed,
              "state_in_bfloat16": state_in_bfloat16,
              "rotary_applied": rotary_applied}
