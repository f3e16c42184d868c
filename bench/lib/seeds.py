"""Seeds as the command line gives them: any whole number up to and past
2**32.  ``jax.random.key`` keeps only the low 32 bits of a larger seed, so
the high bits are folded in."""
from __future__ import annotations

import jax


def seed_key(seed: int, stream: int = 0):
    """A key from all the bits of ``seed``; ``stream`` separates the
    independent draws of one run (weights, batches, probes)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    high = seed >> 32
    while high:
        key = jax.random.fold_in(key, high & 0xFFFFFFFF)
        high >>= 32
    return jax.random.fold_in(key, stream)
