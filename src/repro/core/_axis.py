"""Axis helpers usable under both shard_map and vmap(axis_name=...).

All collective mock-ups are written against these thin wrappers so the same
code path is exercised by (a) single-device vmap semantic tests, (b)
multi-host-device shard_map tests, and (c) the production mesh lowering.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def axis_size(axis_name: str) -> int:
    """Static size of a named axis (trace-time Python int)."""
    return int(lax.axis_size(axis_name))


def axis_index(axis_name: str):
    """Index of this shard along ``axis_name`` (traced int32)."""
    return lax.axis_index(axis_name)


def ring_perm(p: int, shift: int = 1) -> list[tuple[int, int]]:
    """Permutation sending rank i -> rank (i + shift) % p (ICI ring hop)."""
    return [(i, (i + shift) % p) for i in range(p)]


def shift_perm(p: int, shift: int) -> list[tuple[int, int]]:
    """Non-wrapping shift: rank i -> i + shift (ranks without a source
    receive zeros, which ppermute guarantees)."""
    if shift >= 0:
        return [(i, i + shift) for i in range(p - shift)]
    return [(i, i + shift) for i in range(-shift, p)]


def pshift(x, axis_name: str, pairs: list[tuple[int, int]]):
    """``lax.ppermute`` that accepts *partial* permutations everywhere.

    Under shard_map/SPMD, partial source-target pair lists are legal (ranks
    with no source receive zeros) and lower to a single collective-permute.
    The vmap batching rule, however, asserts a complete permutation; there we
    complete the permutation with dummy pairs and mask the fake deliveries
    back to zero — semantics identical, only exercised in single-device
    semantic tests.
    """
    p = axis_size(axis_name)
    if len(pairs) == p:
        return lax.ppermute(x, axis_name, pairs)
    try:
        return lax.ppermute(x, axis_name, pairs)
    except AssertionError:
        pass
    srcs = {s for s, _ in pairs}
    dsts = {d for _, d in pairs}
    free_s = [i for i in range(p) if i not in srcs]
    free_d = [i for i in range(p) if i not in dsts]
    full = list(pairs) + list(zip(free_s, free_d))
    y = lax.ppermute(x, axis_name, full)
    keep = jnp.asarray([i in dsts for i in range(p)])
    mask = keep[axis_index(axis_name)]
    return jnp.where(mask, y, jnp.zeros_like(y))


def tie_to_axis(x, axis_name: str):
    """Make ``x`` a *mapped* operand of ``axis_name``.

    Old jax's ``all_to_all`` batching rule miscomputes when an operand is
    unmapped over the vmap axis (e.g. a constant cotangent entering a
    custom-VJP bwd).  A no-op select against ``axis_index`` ties the value
    to the axis; under shard_map/SPMD it compiles to the identity.
    """
    idx = axis_index(axis_name)
    return jnp.where(idx >= 0, x, jnp.zeros_like(x))


def tree_rounds(p: int) -> int:
    """Number of rounds of a binomial tree over p ranks."""
    r = 0
    while (1 << r) < p:
        r += 1
    return r
