"""Seeds as the command line gives them, turned into JAX keys."""

from __future__ import annotations


def prng_key(seed: int):
    """A JAX key for any whole ``seed`` in [0, 2**64): the low 32 bits
    seed the key and the high 32 bits are folded in."""
    import jax
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)
