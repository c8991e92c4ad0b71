"""The key that every family's weights are made from. The weights themselves
are the family's (``families/<family>/weights.py``): made on the device from
``--seed`` in one jitted call, in the type they are served or trained in, and
made again from the seed by the plain reference, which so takes nothing that
the program has touched."""
import jax


def seed_key(seed):
    """``--seed`` may be a little over 2**31: fold it in as two halves."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
