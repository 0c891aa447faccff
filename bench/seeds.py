"""Seeds: a run's ``--seed`` is any whole number up to a little over
2**31, more than a signed 32-bit key holds."""
from __future__ import annotations


def seed_key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def seed_words(seed: int) -> tuple[int, int]:
    """``seed`` as two 32-bit words, for ``numpy.random.default_rng``."""
    return seed & 0xFFFFFFFF, seed >> 32
