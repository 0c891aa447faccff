"""MPI semantics of the grid's collectives, in numpy, rank by rank.

``x`` is the list of the ``p`` ranks' send buffers, each ``[rows, cols]``.
A function returns each rank's receive buffer, or ``None`` where MPI
leaves it undefined (non-roots of gather and reduce, rank 0 of exscan).
Buffers of the ``V_IN`` ops hold ``p`` blocks of ``n`` rows, one per rank.
"""
from __future__ import annotations

import numpy as np

REFERENCE_OPS = ("allgather", "allreduce", "reducescatter", "alltoall", "bcast",
                 "gather", "scatter", "reduce", "scan", "exscan")
ROOTED = ("bcast", "gather", "scatter", "reduce")
V_IN = ("reducescatter", "alltoall", "scatter")      # input  p*n rows
V_OUT = ("allgather", "alltoall", "gather")          # output p*n rows


def _blocks(a, p):
    return np.split(a, p, axis=0)


def expected(op: str, x: list, root: int = 0) -> list:
    p = len(x)
    total = np.sum(np.stack(x), axis=0, dtype=x[0].dtype)
    if op == "allgather":
        return [np.concatenate(x)] * p
    if op == "allreduce":
        return [total] * p
    if op == "reducescatter":
        return _blocks(total, p)
    if op == "alltoall":
        return [np.concatenate([_blocks(x[s], p)[r] for s in range(p)])
                for r in range(p)]
    if op == "bcast":
        return [x[root]] * p
    if op == "gather":
        return [np.concatenate(x) if r == root else None for r in range(p)]
    if op == "scatter":
        return _blocks(x[root], p)
    if op == "reduce":
        return [total if r == root else None for r in range(p)]
    if op == "scan":
        return list(np.cumsum(np.stack(x), axis=0, dtype=x[0].dtype))
    if op == "exscan":
        inc = np.cumsum(np.stack(x), axis=0, dtype=x[0].dtype)
        return [None] + list(inc[:-1])
    raise ValueError(f"no MPI model of {op!r}")


def link_bytes(op: str, p: int, nbytes: int) -> float:
    """Least bytes the busiest chip must send or receive over its links
    for one call with ``nbytes`` of payload per rank (``p * nbytes`` of
    input for the ``V_IN`` ops), whatever the schedule: a lower bound,
    so that time at the link peak never beats it."""
    return {
        "allgather": (p - 1) * nbytes,          # receive p-1 blocks
        "allreduce": 2 * (p - 1) / p * nbytes,  # reduce-scatter + allgather
        "reducescatter": (p - 1) * nbytes,      # p-1 partial blocks in
        "alltoall": (p - 1) * nbytes,           # p-1 blocks in
        "bcast": nbytes,                        # a non-root receives it
        "gather": (p - 1) * nbytes,             # the root receives p-1
        "scatter": (p - 1) * nbytes,            # the root sends p-1
        "reduce": nbytes,                       # the root receives a sum
        "scan": nbytes,                         # rank p-1 receives a sum
        "exscan": nbytes,
    }[op]
