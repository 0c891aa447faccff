"""Collective implementations: defaults + guideline mock-ups (GL1-GL22 + ⊕).

This is the PGMPITuneLib mock-up catalog re-derived for the ``jax.lax``
collective vocabulary (see DESIGN.md §3).  Every function here operates on
*per-shard* arrays inside ``shard_map`` (or ``vmap(axis_name=...)`` in the
semantic tests) and communicates over a single named mesh axis.

Conventions (axis size ``p``, per-shard payload ``n`` rows along dim 0):

=============== =============================== ===========================
op              input (per shard)               output (per shard)
=============== =============================== ===========================
allgather       ``[n, ...]``                    ``[p*n, ...]``
allreduce       ``[n, ...]``                    ``[n, ...]`` (sum over axis)
reducescatter   ``[p*n, ...]``                  ``[n, ...]``
alltoall        ``[p*n, ...]``                  ``[p*n, ...]``
bcast           ``[n, ...]``                    ``[n, ...]`` (root's values)
gather          ``[n, ...]``                    ``[p*n, ...]`` (valid on root)
scatter         ``[p*n, ...]`` (valid on root)  ``[n, ...]``
reduce          ``[n, ...]``                    ``[n, ...]`` (valid on root)
scan            ``[n, ...]``                    inclusive prefix over ranks
exscan          ``[n, ...]``                    exclusive prefix over ranks
=============== =============================== ===========================

Rooted collectives have no TPU/XLA primitive; their "default" is the
composition XLA itself would pick (documented per op).  "valid on root"
means only the root shard's output is part of the contract; non-root
shards may receive the full result (superset semantics) or zeros.

Irregular ("v") emulations attach the paper's ``2pI`` count/displacement
metadata as a real (tiny) collective kept alive through
``lax.optimization_barrier`` so its cost stays visible in the HLO.

MOCK-UPS CALL CONCRETE SUB-IMPLEMENTATIONS, NEVER THE DISPATCHER — exactly
as PGMPITuneLib mock-ups call ``PMPI_*`` (library defaults), not the
intercepted entry points.  This rules out recursive re-tuning.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.core._axis import (axis_index, axis_size, pshift, ring_perm,
                              shift_perm)

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _n_rows(x) -> int:
    return int(x.shape[0])


def _pad_rows(x, n_pad: int):
    """Zero-pad dim 0 of ``x`` up to ``n_pad`` rows."""
    n = _n_rows(x)
    if n_pad == n:
        return x
    pad = [(0, n_pad - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


def _one_hot_place(x, axis: str, scale: int = 1):
    """Place ``x`` at row-offset ``axis_index*n`` inside a ``p*n`` zero buffer
    (the paper's GL3/GL13 "p-times-larger send buffer").  Additive placement
    replaces the paper's MPI_BOR (identical result, MXU/float friendly)."""
    p = axis_size(axis)
    n = _n_rows(x)
    buf = jnp.zeros((p * n,) + x.shape[1:], x.dtype)
    idx = axis_index(axis)
    return lax.dynamic_update_slice(buf, x, (idx * n,) + (0,) * (x.ndim - 1))


def _v_metadata(x, axis: str):
    """The irregular-collective count/displacement exchange: ``2p`` ints of
    metadata all-gathered over the axis (Table 1's ``2pI`` term)."""
    n = _n_rows(x)
    meta = jnp.stack(  # (count, displ)
        [jnp.int32(n), (n * axis_index(axis)).astype(jnp.int32)])
    return lax.all_gather(meta, axis, axis=0, tiled=True)


def _attach(y, meta):
    """Keep the metadata exchange alive in the HLO (prevent DCE) without
    touching the payload values."""
    y, _ = lax.optimization_barrier((y, meta))
    return y


def _rel(idx, root: int, p: int):
    """Rank relative to a static root (binomial schedules)."""
    if root == 0:
        return idx
    return (idx - root) % p


def _abs_perm(rel_pairs, root: int, p: int):
    """Map relative-rank (src, dst) pairs to absolute ranks."""
    if root == 0:
        return rel_pairs
    return [((s + root) % p, (d + root) % p) for (s, d) in rel_pairs]


def _is_pow2(p: int) -> bool:
    return p & (p - 1) == 0


# ---------------------------------------------------------------------------
# defaults (what an untuned lowering would emit)
# ---------------------------------------------------------------------------


def allgather_default(x, axis: str, *, inner_axis: str | None = None, **_):
    """Flat: one ``all_gather``.  Hierarchical (``inner_axis`` set): the
    untuned two-axis lowering — gather the intra tier, then the inter
    tier, yielding outer-major block order (what one flat gather over the
    joint ``(axis, inner_axis)`` group produces)."""
    if inner_axis is not None:
        x = lax.all_gather(x, inner_axis, axis=0, tiled=True)
    return lax.all_gather(x, axis, axis=0, tiled=True)


def allreduce_default(x, axis: str, *, inner_axis: str | None = None, **_):
    return lax.psum(x, axis if inner_axis is None else (axis, inner_axis))


def reducescatter_default(x, axis: str, *, inner_axis: str | None = None,
                          **_):
    y = lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    if inner_axis is not None:
        y = lax.psum_scatter(y, inner_axis, scatter_dimension=0, tiled=True)
    return y


def alltoall_default(x, axis: str, **_):
    return lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)


def bcast_as_psum(x, axis: str, *, root: int = 0, **_):
    """XLA's canonical broadcast-from-root: select + all-reduce."""
    idx = axis_index(axis)
    return lax.psum(jnp.where(idx == root, x, jnp.zeros_like(x)), axis)


def gather_as_allgather(x, axis: str, *, root: int = 0, **_):
    """(GL11) root-gather served by all-gather; non-roots get a superset."""
    del root
    return lax.all_gather(x, axis, axis=0, tiled=True)


def scatter_as_alltoall(x, axis: str, *, root: int = 0, **_):
    """Default scatter: mask non-root buffers, all-to-all, keep segment root.
    Single primitive; moves p*n where a tree scatter moves n*(p-1)/p·log p."""
    idx = axis_index(axis)
    xz = jnp.where(idx == root, x, jnp.zeros_like(x))
    y = lax.all_to_all(xz, axis, split_axis=0, concat_axis=0, tiled=True)
    n = _n_rows(x) // axis_size(axis)
    return lax.slice_in_dim(y, root * n, (root + 1) * n, axis=0)


def reduce_as_allreduce(x, axis: str, *, root: int = 0, **_):
    """(GL14) rooted reduce served by psum; non-roots ignore the result."""
    del root
    return lax.psum(x, axis)


def scan_default(x, axis: str, *, op: str = "add", **_):
    """Inclusive prefix over ranks — Hillis–Steele with log2(p) ppermutes."""
    p = axis_size(axis)
    idx = axis_index(axis)
    y = x
    d = 1
    while d < p:
        shifted = pshift(y, axis, shift_perm(p, d))
        if op == "add":
            y = y + shifted  # ppermute zero-fill is the additive identity
        elif op == "max":
            y = jnp.where(idx >= d, jnp.maximum(y, shifted), y)
        else:
            raise ValueError(f"unsupported scan op {op!r}")
        d *= 2
    return y


def exscan_default(x, axis: str, *, op: str = "add", **_):
    """Exclusive prefix: shift inputs one rank up, then inclusive scan."""
    p = axis_size(axis)
    shifted = pshift(x, axis, shift_perm(p, 1))
    if op == "max":
        idx = axis_index(axis)
        neg = jnp.full_like(x, -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
                            else jnp.iinfo(x.dtype).min)
        shifted = jnp.where(idx == 0, neg, shifted)
    return scan_default(shifted, axis, op=op)


# ---------------------------------------------------------------------------
# MPI_Allgather mock-ups
# ---------------------------------------------------------------------------


def allgather_as_gather_bcast(x, axis: str, **_):
    """(GL1) Gather + Bcast."""
    g = gather_as_allgather(x, axis, root=0)
    return bcast_as_psum(g, axis, root=0)


def allgather_as_alltoall(x, axis: str, **_):
    """(GL2) p-times replicated send buffer, then all-to-all."""
    p = axis_size(axis)
    reps = (p,) + (1,) * (x.ndim - 1)
    # the send buffer is materialized: XLA:CPU splits an all-to-all into
    # per-peer slices, and its simplifier rewrote a slice of a tiled
    # transpose into a transpose of another layout than its sibling
    # slices, which the HLO verifier rejects
    big = lax.optimization_barrier(jnp.tile(x, reps))
    return lax.all_to_all(big, axis, split_axis=0, concat_axis=0, tiled=True)


def allgather_as_allreduce(x, axis: str, **_):
    """(GL3) one-hot placement into a p·n zero buffer, then all-reduce."""
    return lax.psum(_one_hot_place(x, axis), axis)


def allgather_as_allgatherv(x, axis: str, **_):
    """(GL4) irregular emulation: counts/displs metadata + padded gather."""
    meta = _v_metadata(x, axis)
    y = lax.all_gather(x, axis, axis=0, tiled=True)
    return _attach(y, meta)


def allgather_as_ring(x, axis: str, **_):
    """(⊕) (p-1)-step neighbour ring — ICI-local traffic only (the
    BlueGene/Q-style topology-native schedule the paper could not inject)."""
    p = axis_size(axis)
    n = _n_rows(x)
    idx = axis_index(axis)
    buf = _one_hot_place(x, axis)
    cur = x
    for s in range(1, p):
        cur = pshift(cur, axis, ring_perm(p, 1))
        src = (idx - s) % p  # originating rank of the block received now
        buf = lax.dynamic_update_slice(
            buf, cur, (src * n,) + (0,) * (x.ndim - 1))
    return buf


def allgather_as_doubling(x, axis: str, **_):
    """(⊕) recursive doubling: log2(p) rounds, partner i XOR d.  Requires a
    power-of-two axis; the registry guards this."""
    p = axis_size(axis)
    assert _is_pow2(p), "recursive doubling needs power-of-two axis"
    buf = _one_hot_place(x, axis)
    d = 1
    while d < p:
        pairs = [(i, i ^ d) for i in range(p)]
        buf = buf + pshift(buf, axis, pairs)
        d *= 2
    return buf


# ---------------------------------------------------------------------------
# MPI_Allreduce mock-ups
# ---------------------------------------------------------------------------


def allreduce_as_reduce_bcast(x, axis: str, **_):
    """(GL5) Reduce + Bcast through the library defaults."""
    r = reduce_as_allreduce(x, axis, root=0)
    return bcast_as_psum(r, axis, root=0)


def allreduce_as_tree_reduce_bcast(x, axis: str, **_):
    """(⊕/GL5-variant) binomial-tree Reduce + binomial-tree Bcast — the
    schedule an MPI library's 'nonoverlapping' algorithm uses (Fig. 7)."""
    r = reduce_as_tree(x, axis, root=0)
    return bcast_as_tree(r, axis, root=0)


def allreduce_as_rsb_allgather(x, axis: str, **_):
    """(GL6) Reduce_scatter_block + Allgather (ring / Rabenseifner).  Pads
    n up to a multiple of p (the paper's "small c for padding")."""
    p = axis_size(axis)
    n = _n_rows(x)
    n_pad = -(-n // p) * p
    xp = _pad_rows(x, n_pad)
    rs = lax.psum_scatter(xp, axis, scatter_dimension=0, tiled=True)
    y = lax.all_gather(rs, axis, axis=0, tiled=True)
    return lax.slice_in_dim(y, 0, n, axis=0)


def allreduce_as_rs_allgatherv(x, axis: str, *, chunk: int = 1, **_):
    """(GL7) Reduce_scatter + Allgatherv with round-robin chunks of size
    ``chunk`` (the paper's C) — the Fig.-7 winner.  Emulated with chunk-
    aligned padding + the 2pI metadata exchange."""
    p = axis_size(axis)
    n = _n_rows(x)
    c = max(1, min(int(chunk), n))
    k = -(-(-(-n // c)) // p)  # ceil(ceil(n/c)/p) chunks per rank
    n_pad = p * k * c
    xp = _pad_rows(x, n_pad)
    meta = _v_metadata(x, axis)
    rs = lax.psum_scatter(xp, axis, scatter_dimension=0, tiled=True)
    y = lax.all_gather(rs, axis, axis=0, tiled=True)
    return _attach(lax.slice_in_dim(y, 0, n, axis=0), meta)


def allreduce_as_doubling(x, axis: str, **_):
    """(⊕) recursive-doubling all-reduce: log2(p)·(α + nβ) — latency-optimal
    for small payloads where the ring's 2(p-1)α dominates."""
    p = axis_size(axis)
    assert _is_pow2(p), "recursive doubling needs power-of-two axis"
    y = x
    d = 1
    while d < p:
        y = y + pshift(y, axis, [(i, i ^ d) for i in range(p)])
        d *= 2
    return y


# ---------------------------------------------------------------------------
# MPI_Alltoall mock-ups
# ---------------------------------------------------------------------------


def alltoall_as_alltoallv(x, axis: str, **_):
    """(GL8) irregular emulation: metadata + padded all-to-all."""
    meta = _v_metadata(x, axis)
    y = lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)
    return _attach(y, meta)


def alltoall_as_ppermute(x, axis: str, **_):
    """(⊕) (p-1) shifted-ring rounds; latency-regime alternative to the
    bisection-limited monolithic all-to-all."""
    p = axis_size(axis)
    n = _n_rows(x) // p
    idx = axis_index(axis)
    zeros = (0,) * (x.ndim - 1)
    out = jnp.zeros_like(x)
    # my own chunk stays in place
    own = lax.dynamic_slice(x, (idx * n,) + zeros, (n,) + x.shape[1:])
    out = lax.dynamic_update_slice(out, own, (idx * n,) + zeros)
    for s in range(1, p):
        dst = (idx + s) % p
        piece = lax.dynamic_slice(x, (dst * n,) + zeros, (n,) + x.shape[1:])
        recv = pshift(piece, axis, ring_perm(p, s))
        src = (idx - s) % p
        out = lax.dynamic_update_slice(out, recv, (src * n,) + zeros)
    return out


# ---------------------------------------------------------------------------
# MPI_Bcast mock-ups
# ---------------------------------------------------------------------------


def bcast_as_allgatherv(x, axis: str, *, root: int = 0, **_):
    """(GL9) root contributes n, everyone else 0, via allgatherv: emulated as
    masked all-gather + static segment select + metadata."""
    idx = axis_index(axis)
    n = _n_rows(x)
    xz = jnp.where(idx == root, x, jnp.zeros_like(x))
    meta = _v_metadata(x, axis)
    y = lax.all_gather(xz, axis, axis=0, tiled=True)
    return _attach(lax.slice_in_dim(y, root * n, (root + 1) * n, axis=0), meta)


def bcast_as_scatter_allgather(x, axis: str, *, root: int = 0, **_):
    """(GL10) Scatter + Allgather (van de Geijn) — bandwidth-optimal large-
    message broadcast.  Pads n to a multiple of p."""
    p = axis_size(axis)
    n = _n_rows(x)
    n_pad = -(-n // p) * p
    xp = _pad_rows(x, n_pad)
    sc = scatter_as_alltoall(xp, axis, root=root)
    y = lax.all_gather(sc, axis, axis=0, tiled=True)
    return lax.slice_in_dim(y, 0, n, axis=0)


def bcast_as_tree(x, axis: str, *, root: int = 0, **_):
    """(⊕) binomial-tree broadcast: ceil(log2 p) ppermute rounds."""
    p = axis_size(axis)
    idx = axis_index(axis)
    y = jnp.where(idx == root, x, jnp.zeros_like(x))
    d = 1
    while d < p:
        rel_pairs = [(r, r + d) for r in range(d) if r + d < p]
        y = y + pshift(y, axis, _abs_perm(rel_pairs, root, p))
        d *= 2
    return y


# ---------------------------------------------------------------------------
# MPI_Gather mock-ups
# ---------------------------------------------------------------------------


def gather_as_gatherv(x, axis: str, *, root: int = 0, **_):
    """(GL12) irregular emulation: metadata + gather; non-roots zeroed to
    keep rooted semantics observable."""
    meta = _v_metadata(x, axis)
    y = lax.all_gather(x, axis, axis=0, tiled=True)
    idx = axis_index(axis)
    y = jnp.where(idx == root, y, jnp.zeros_like(y))
    return _attach(y, meta)


def gather_as_reduce(x, axis: str, *, root: int = 0, **_):
    """(GL13) one-hot placement + rooted reduce (additive ≡ the paper's BOR
    on disjoint supports)."""
    return reduce_as_allreduce(_one_hot_place(x, axis), axis, root=root)


def gather_as_tree(x, axis: str, *, root: int = 0, **_):
    """(⊕) binomial-tree gather on a p·n zero-merged buffer."""
    p = axis_size(axis)
    idx = axis_index(axis)
    rel = _rel(idx, root, p)
    del rel  # merge is positional; masking handled by zero-fill
    y = _one_hot_place(x, axis)
    d = 1
    while d < p:
        rel_pairs = [(r + d, r) for r in range(0, p, 2 * d) if r + d < p]
        y = y + pshift(y, axis, _abs_perm(rel_pairs, root, p))
        d *= 2
    return y


# ---------------------------------------------------------------------------
# MPI_Reduce mock-ups
# ---------------------------------------------------------------------------


def reduce_as_rsb_gather(x, axis: str, *, root: int = 0, **_):
    """(GL15) Reduce_scatter_block + Gather (padded)."""
    p = axis_size(axis)
    n = _n_rows(x)
    n_pad = -(-n // p) * p
    xp = _pad_rows(x, n_pad)
    rs = lax.psum_scatter(xp, axis, scatter_dimension=0, tiled=True)
    y = gather_as_allgather(rs, axis, root=root)
    return lax.slice_in_dim(y, 0, n, axis=0)


def reduce_as_rs_gatherv(x, axis: str, *, root: int = 0, chunk: int = 1, **_):
    """(GL16) chunked Reduce_scatter + Gatherv (paper's C, metadata cost)."""
    p = axis_size(axis)
    n = _n_rows(x)
    c = max(1, min(int(chunk), n))
    k = -(-(-(-n // c)) // p)
    n_pad = p * k * c
    xp = _pad_rows(x, n_pad)
    meta = _v_metadata(x, axis)
    rs = lax.psum_scatter(xp, axis, scatter_dimension=0, tiled=True)
    y = gather_as_allgather(rs, axis, root=root)
    return _attach(lax.slice_in_dim(y, 0, n, axis=0), meta)


def reduce_as_tree(x, axis: str, *, root: int = 0, **_):
    """(⊕) binomial-tree reduce to root: log2(p) rounds."""
    p = axis_size(axis)
    y = x
    d = 1
    while d < p:
        rel_pairs = [(r + d, r) for r in range(0, p, 2 * d) if r + d < p]
        y = y + pshift(y, axis, _abs_perm(rel_pairs, root, p))
        d *= 2
    return y


# ---------------------------------------------------------------------------
# MPI_Reduce_scatter_block mock-ups
# ---------------------------------------------------------------------------


def rsb_as_reduce_scatter(x, axis: str, **_):
    """(GL17) Reduce + Scatter through the defaults."""
    r = reduce_as_allreduce(x, axis, root=0)
    return scatter_as_alltoall(r, axis, root=0)


def rsb_as_reduce_scatter_irr(x, axis: str, **_):
    """(GL18) irregular reduce_scatter emulation: metadata + psum_scatter."""
    meta = _v_metadata(x, axis)
    y = lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    return _attach(y, meta)


def rsb_as_allreduce(x, axis: str, **_):
    """(GL19) Allreduce + keep my block."""
    p = axis_size(axis)
    n = _n_rows(x) // p
    y = lax.psum(x, axis)
    idx = axis_index(axis)
    return lax.dynamic_slice(
        y, (idx * n,) + (0,) * (x.ndim - 1), (n,) + x.shape[1:])


# ---------------------------------------------------------------------------
# MPI_Scan mock-ups
# ---------------------------------------------------------------------------


def scan_as_exscan_reducelocal(x, axis: str, *, op: str = "add", **_):
    """(GL20) Exscan + local reduction."""
    ex = exscan_default(x, axis, op=op)
    if op == "add":
        return ex + x
    if op == "max":
        return jnp.maximum(ex, x)
    raise ValueError(f"unsupported scan op {op!r}")


# ---------------------------------------------------------------------------
# MPI_Scatter mock-ups
# ---------------------------------------------------------------------------


def scatter_as_bcast(x, axis: str, *, root: int = 0, **_):
    """(GL21) Bcast the whole buffer + local slice."""
    p = axis_size(axis)
    n = _n_rows(x) // p
    y = bcast_as_psum(x, axis, root=root)
    idx = axis_index(axis)
    return lax.dynamic_slice(
        y, (idx * n,) + (0,) * (x.ndim - 1), (n,) + x.shape[1:])


def scatter_as_scatterv(x, axis: str, *, root: int = 0, **_):
    """(GL22) irregular emulation: metadata + scatter."""
    meta = _v_metadata(x, axis)
    return _attach(scatter_as_alltoall(x, axis, root=root), meta)


def scatter_as_tree(x, axis: str, *, root: int = 0, **_):
    """(⊕) binomial-tree scatter: root halves its range every round."""
    p = axis_size(axis)
    assert _is_pow2(p), "tree scatter needs power-of-two axis"
    n = _n_rows(x) // p
    idx = axis_index(axis)
    rel = _rel(idx, root, p)
    zeros = (0,) * (x.ndim - 1)
    # rotate into relative-rank layout so tree ranges stay contiguous;
    # rank rel r finally reads chunk (r+root)%p == its absolute chunk.
    y = jnp.roll(x, -root * n, axis=0)
    y = jnp.where(idx == root, y, jnp.zeros_like(y))
    d = p // 2
    while d >= 1:
        rel_pairs = [(r, r + d) for r in range(0, p, 2 * d)]
        send = lax.dynamic_slice(
            y, ((rel + d) % p * n,) + zeros, (d * n,) + x.shape[1:])
        recv = pshift(send, axis, _abs_perm(rel_pairs, root, p))
        keep = lax.dynamic_slice(y, (rel * n,) + zeros, (d * n,) + x.shape[1:])
        y = lax.dynamic_update_slice(y, keep + recv, (rel * n,) + zeros)
        d //= 2
    return lax.dynamic_slice(y, (rel * n,) + zeros, (n,) + x.shape[1:])


# ---------------------------------------------------------------------------
# fused collective-matmul ops (latency-hiding mock-ups, kernels/)
# ---------------------------------------------------------------------------
#
# Three extra ops extend the vocabulary beyond MPI's: a matmul fused to the
# collective feeding (or consuming) it.  Semantics (the second operand is
# passed by keyword; per-shard shapes, axis size ``p``):
#
#   allgather_matmul       x [n, K], w [K, M]     -> all_gather(x) @ w [p*n, M]
#   matmul_reducescatter   x [p*n, K], w [K, M]   -> reduce_scatter(x @ w) [n, M]
#   matmul_accumulate      w [K/p, M], x [T, K]   -> x @ all_gather(w) [T, M]
#   matmul_reducescatter_2d
#       w [K, M/d] over ag axis (size d), x [T, K], rs axis (size q)
#       -> reduce_scatter(x @ all_gather(w, cols, ag), rows, rs) [T/q, M]
#       (xpose=True: g [T/q, M] over ag axis, x [T, K]
#        -> reduce_scatter(all_gather(g, rows, ag)T @ x, rows, rs) [M/d, K])
#
# ``default`` is the unfused composition today's dist/ops emit; ``fused_ring``
# (and ``fused_ring2d`` for the two-axis op) is the
# kernels/collective_matmul.py ring schedule that overlaps each chunk's
# transfer with the previous chunk's matmul.  The tuner arbitrates the two via
# the overlap-aware cost model (max(comm, compute) per step instead of sum).
# Note ``matmul_accumulate`` and ``matmul_reducescatter_2d`` take the
# STREAMED operand (the K-dim / column-block weight shard, or the xpose
# cotangent shard) first — the dispatcher keys on the bytes the collective
# moves over its OUTER axis.


def allgather_matmul_default(x, axis: str, *, w, return_gathered: bool = False,
                             **_):
    """Unfused composition: all_gather then one dense matmul."""
    g = lax.all_gather(x, axis, axis=0, tiled=True)
    out = jnp.matmul(g, w)
    return (out, g) if return_gathered else out


def allgather_matmul_fused_ring(x, axis: str, *, w,
                                return_gathered: bool = False, **_):
    """(⊕) ring allgather-matmul: chunk s+1 in flight while chunk s is on
    the MXU.  The same ppermute ring on every backend."""
    from repro.kernels import collective_matmul as cmm
    return cmm.ring_allgather_matmul(x, w, axis,
                                     return_gathered=return_gathered)


def matmul_reducescatter_default(x, axis: str, *, w, **_):
    """Unfused composition: one dense matmul then reduce-scatter."""
    return lax.psum_scatter(jnp.matmul(x, w), axis, scatter_dimension=0,
                            tiled=True)


def matmul_reducescatter_fused_ring(x, axis: str, *, w, **_):
    """(⊕) ring matmul-reducescatter: the travelling accumulator is in
    flight while the next block's contribution is computed."""
    from repro.kernels import collective_matmul as cmm
    return cmm.ring_matmul_reducescatter(x, w, axis)


def matmul_accumulate_default(w, axis: str, *, x,
                              return_gathered: bool = False, **_):
    """Unfused composition: all_gather the K-dim weight shards, then one
    dense matmul over the full contraction."""
    full = lax.all_gather(w, axis, axis=0, tiled=True)
    out = jnp.matmul(x, full)
    return (out, full) if return_gathered else out


def matmul_accumulate_fused_ring(w, axis: str, *, x,
                                 return_gathered: bool = False, **_):
    """(⊕) accumulate ring: weight block s+1 in flight while block s's
    partial product accumulates (kernels/collective_matmul.py)."""
    from repro.kernels import collective_matmul as cmm
    return cmm.ring_matmul_accumulate(x, w, axis,
                                      return_gathered=return_gathered)


def matmul_reducescatter_2d_default(w, axis: str, *, x, rs_axis: str,
                                    xpose: bool = False,
                                    return_gathered: bool = False, **_):
    """Unfused 2-D composition: gather the streamed operand over ``axis``
    (the outer axis the dispatcher keys on), one dense matmul, then
    reduce-scatter the output rows over ``rs_axis``.

    ``xpose=False``: w ``[K, m_loc]`` col-gathered -> psum_scatter(x @ W).
    ``xpose=True``: the payload is the cotangent shard g ``[t_loc, M]``
    row-gathered and CONTRACTED -> psum_scatter(Gᵀ @ x) — the transpose
    schedule of the paired VJP.
    """
    if xpose:
        full = lax.all_gather(w, axis, axis=0, tiled=True)
        return lax.psum_scatter(jnp.matmul(jnp.swapaxes(full, 0, 1), x),
                                rs_axis, scatter_dimension=0, tiled=True)
    full = lax.all_gather(w, axis, axis=1, tiled=True)
    out = lax.psum_scatter(jnp.matmul(x, full), rs_axis,
                           scatter_dimension=0, tiled=True)
    return (out, full) if return_gathered else out


def matmul_reducescatter_2d_fused_ring(w, axis: str, *, x, rs_axis: str,
                                       xpose: bool = False,
                                       return_gathered: bool = False, **_):
    """(⊕) nested 2-D ring: outer weight (or cotangent) stream over
    ``axis``, inner matmul-reducescatter (or contract-stream) over
    ``rs_axis``, issue-before-consume on both axes
    (kernels/collective_matmul.py)."""
    from repro.kernels import collective_matmul as cmm
    if xpose:
        return cmm.ring_matmul_reducescatter_2d_t(w, x, rs_axis, axis)
    return cmm.ring_matmul_reducescatter_2d(
        x, w, rs_axis, axis, return_gathered=return_gathered)


# ---------------------------------------------------------------------------
# quantized-wire mock-ups (wire_q8 / wire_fp8): the ring schedules with the
# travelling operand compressed to an 8-bit wire dtype + per-block scales
# (kernels/quant.py).  Quantize-on-send, dequantize-on-receive, reductions
# accumulate in f32 after dequant.  These are APPROXIMATE impls: their
# admissibility is gated by the selfcheck numeric-tolerance check (a cell
# that breaks its wire tolerance demotes the impl via ``demote`` below,
# exactly like a failed guideline).
# ---------------------------------------------------------------------------


def allgather_wire(x, axis: str, *, wire_dtype: str = "int8", **_):
    """(⊕) ring allgather over the quantized wire: each rank's chunk is
    quantized ONCE at its origin and the (values, scales) pair travels the
    ring unchanged; the own chunk never crosses the wire and stays exact."""
    from repro.kernels import quant as Q
    p = axis_size(axis)
    if p == 1:
        return x
    n = _n_rows(x)
    idx = axis_index(axis)
    zeros = (0,) * (x.ndim - 1)
    out = jnp.zeros((p * n,) + x.shape[1:], x.dtype)
    out = lax.dynamic_update_slice(out, x, (idx * n,) + zeros)
    q, sc = Q.quantize(x, wire_dtype)
    for s in range(1, p):
        q = pshift(q, axis, ring_perm(p, 1))
        sc = pshift(sc, axis, ring_perm(p, 1))
        src = (idx - s) % p
        out = lax.dynamic_update_slice(out, Q.dequantize(q, sc, x.dtype),
                                       (src * n,) + zeros)
    return out


def reducescatter_wire(x, axis: str, *, wire_dtype: str = "int8", **_):
    """(⊕) ring reduce-scatter over the quantized wire: the travelling
    accumulator is requantized before every hop; local contributions are
    added to the DEQUANTIZED f32 accumulator (accumulate-in-f32 rule)."""
    from repro.kernels import quant as Q
    p = axis_size(axis)
    if p == 1:
        return x
    rows = _n_rows(x)
    n = rows // p
    idx = axis_index(axis)
    acc = None
    for s in range(p):
        blk_id = (idx + (p - 1 - s)) % p
        blk = lax.dynamic_slice(x, (blk_id * n,) + (0,) * (x.ndim - 1),
                                (n,) + x.shape[1:])
        contrib = blk.astype(jnp.float32)
        acc = contrib if acc is None else acc + contrib
        if s < p - 1:
            q, sc = Q.quantize(acc, wire_dtype)
            q = pshift(q, axis, ring_perm(p, 1))
            sc = pshift(sc, axis, ring_perm(p, 1))
            acc = Q.dequantize(q, sc, jnp.float32)
    return acc.astype(x.dtype)


def allreduce_wire(x, axis: str, *, wire_dtype: str = "int8", **_):
    """(⊕) quantized-wire allreduce = padded wire reduce-scatter + wire
    allgather (the GL6 decomposition with both phases on the 8-bit wire)."""
    p = axis_size(axis)
    if p == 1:
        return x
    n = _n_rows(x)
    k = -(-n // p)
    xp = _pad_rows(x, k * p)
    red = reducescatter_wire(xp, axis, wire_dtype=wire_dtype)
    out = allgather_wire(red, axis, wire_dtype=wire_dtype)
    return out[:n] if out.shape[0] != n else out


def allgather_matmul_wire(x, axis: str, *, w, wire_dtype: str = "int8",
                          return_gathered: bool = False, **_):
    """(⊕) ring allgather-matmul with the activation chunk on the
    quantized wire (kernels/collective_matmul.py tier-1c)."""
    from repro.kernels import collective_matmul as cmm
    return cmm.ring_allgather_matmul_wire(
        x, w, axis, wire_dtype=wire_dtype, return_gathered=return_gathered)


def matmul_reducescatter_wire(x, axis: str, *, w, wire_dtype: str = "int8",
                              **_):
    """(⊕) ring matmul-reducescatter with the travelling accumulator on
    the quantized wire (requantized per hop, f32 accumulate)."""
    from repro.kernels import collective_matmul as cmm
    return cmm.ring_matmul_reducescatter_wire(x, w, axis,
                                              wire_dtype=wire_dtype)


def matmul_accumulate_wire(w, axis: str, *, x, wire_dtype: str = "int8",
                           return_gathered: bool = False, **_):
    """(⊕) accumulate ring with the weight block on the quantized wire."""
    from repro.kernels import collective_matmul as cmm
    return cmm.ring_matmul_accumulate_wire(
        x, w, axis, wire_dtype=wire_dtype, return_gathered=return_gathered)


# ---------------------------------------------------------------------------
# hierarchical (two-tier) mock-ups — MPIX_* extension family.
#
# These are the ONLY impls that take a second axis: ``axis`` is the OUTER
# (inter-tier, slow) axis and ``inner_axis`` the INNER (intra-tier, fast)
# one.  They decompose a joint-group collective into per-tier ring stages
# (kernels/hierarchical.py) so the bulk of the bytes stay on the fast
# tier; admissibility is gated on ``Impl.hier`` — a flat mock-up must
# never be offered a two-axis cell (it would silently reduce over one
# axis only), and a hier mock-up is meaningless on a flat cell.
# ---------------------------------------------------------------------------


def _need_inner(name: str, inner_axis):
    if inner_axis is None:
        raise ValueError(
            f"{name} is a hierarchical mock-up: it needs inner_axis= "
            "(the intra-tier axis) in addition to the outer axis")


def allreduce_hier(x, axis: str, *, inner_axis: str | None = None, **_):
    """(⊕ MPIX_rs_ar_ag) RS-intra → AR-inter → AG-intra."""
    _need_inner("MPIX_rs_ar_ag", inner_axis)
    from repro.kernels import hierarchical as H
    return H.hier_allreduce(x, axis, inner_axis)


def allgather_hier(x, axis: str, *, inner_axis: str | None = None, **_):
    """(⊕ MPIX_ag_ag) AG-intra → AG-inter (outer-major block order)."""
    _need_inner("MPIX_ag_ag", inner_axis)
    from repro.kernels import hierarchical as H
    return H.hier_allgather(x, axis, inner_axis)


def reducescatter_hier(x, axis: str, *, inner_axis: str | None = None, **_):
    """(⊕ MPIX_rs_rs) RS-inter → RS-intra (the MPIX_ag_ag dual)."""
    _need_inner("MPIX_rs_rs", inner_axis)
    from repro.kernels import hierarchical as H
    return H.hier_reduce_scatter(x, axis, inner_axis)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Impl:
    """One algorithm for one logical collective."""
    name: str
    op: str
    fn: Callable
    guideline: str | None  # "GL<k>", "EXT" (⊕), or None for the default
    # extra scratch bytes(payload_bytes, p) — the Table-1 memory model.
    extra_bytes: Callable[[int, int], int]
    requires_pow2: bool = False
    desc: str = ""
    # wire dtype of a quantized-wire mock-up ("int8" / "float8_e4m3fn");
    # None = the wire carries the compute dtype.  Non-None marks the impl
    # accuracy-conditional: selfcheck's tolerance gate may demote it.
    wire_dtype: str | None = None
    # True for the two-axis (hierarchical) mock-ups: the impl REQUIRES
    # ``inner_axis=`` and is only admissible on hierarchical cells
    # (``OpCell.hier``); flat impls are only admissible on flat cells.
    # The default impl handles both worlds itself.
    hier: bool = False

    def __call__(self, x, axis, **kw):
        return self.fn(x, axis, **kw)


_I = 4  # extent of an int32 "MPI_INT" (Table 1's I)


def _nb0(nbytes: int, p: int) -> int:  # no extra memory
    del nbytes, p
    return 0


def _reg() -> dict[str, dict[str, Impl]]:
    def mk(name, op, fn, gl, extra, pow2=False, desc="", wire=None,
           hier=False):
        return Impl(name, op, fn, gl, extra, pow2, desc, wire, hier)

    # quantized-wire mock-ups share one family shape: MPIX_-style name
    # (wire_q8 / wire_fp8 — the MPIX_ prefix marks a beyond-the-standard
    # extension, like MPIX_Allreduce_q8), EXT guideline, wire_dtype bound
    # via partial and recorded on the Impl for the costmodel / selfcheck.
    _WIRES = (("wire_q8", "int8"), ("wire_fp8", "float8_e4m3fn"))

    def mk_wire(op, fn, extra, desc):
        return [mk(nm, op, partial(fn, wire_dtype=wd), "EXT", extra,
                   desc=f"MPIX_{op}_{nm[5:]}: {desc}", wire=wd)
                for nm, wd in _WIRES]

    r: dict[str, dict[str, Impl]] = {}

    r["allgather"] = {i.name: i for i in [
        mk("default", "allgather", allgather_default, None, _nb0,
           desc="lax.all_gather (XLA ring)"),
        mk("allgather_as_gather_bcast", "allgather", allgather_as_gather_bcast,
           "GL1", _nb0),
        mk("allgather_as_alltoall", "allgather", allgather_as_alltoall,
           "GL2", lambda n, p: p * n, desc="p× larger send buffer"),
        mk("allgather_as_allreduce", "allgather", allgather_as_allreduce,
           "GL3", lambda n, p: p * n, desc="p× larger send buffer"),
        mk("allgather_as_allgatherv", "allgather", allgather_as_allgatherv,
           "GL4", lambda n, p: 2 * p * _I, desc="displs+recvcounts"),
        mk("allgather_as_ring", "allgather", allgather_as_ring,
           "EXT", lambda n, p: p * n),
        mk("allgather_as_doubling", "allgather", allgather_as_doubling,
           "EXT", lambda n, p: p * n, pow2=True),
        mk("MPIX_ag_ag", "allgather", allgather_hier, "EXT",
           lambda n, p: p * n, hier=True,
           desc="hierarchical AG-intra -> AG-inter: node block assembled "
                "on the fast tier, streamed across the slow tier once"),
        *mk_wire("allgather", allgather_wire,
                 lambda n, p: p * n + n // 2,
                 desc="ring with the chunk on the 8-bit wire "
                      "(quantized once at origin)"),
    ]}

    r["allreduce"] = {i.name: i for i in [
        mk("default", "allreduce", allreduce_default, None, _nb0,
           desc="lax.psum"),
        mk("allreduce_as_reduce_bcast", "allreduce", allreduce_as_reduce_bcast,
           "GL5", _nb0),
        mk("allreduce_as_tree_reduce_bcast", "allreduce",
           allreduce_as_tree_reduce_bcast, "EXT", _nb0,
           desc="binomial reduce+bcast ('nonoverlapping')"),
        mk("allreduce_as_rsb_allgather", "allreduce",
           allreduce_as_rsb_allgather, "GL6",
           lambda n, p: (n + p) + (n + p) // p, desc="padded RS + AG"),
        mk("allreduce_as_rs_allgatherv", "allreduce",
           allreduce_as_rs_allgatherv, "GL7",
           lambda n, p: max(n // p + 1, 1) + 2 * p * _I,
           desc="chunked RS + AGv (Fig.7 winner)"),
        mk("allreduce_as_doubling", "allreduce", allreduce_as_doubling,
           "EXT", _nb0, pow2=True, desc="recursive doubling (latency-opt)"),
        mk("MPIX_rs_ar_ag", "allreduce", allreduce_hier, "EXT",
           lambda n, p: n + max(n // p, 1), hier=True,
           desc="hierarchical RS-intra -> AR-inter -> AG-intra: full "
                "buffer only moves on the fast tier; 1/q of it crosses "
                "the slow tier"),
        *mk_wire("allreduce", allreduce_wire,
                 lambda n, p: (n + p) + (n + p) // p,
                 desc="padded wire RS + wire AG (GL6 shape, 8-bit wire)"),
    ]}

    r["alltoall"] = {i.name: i for i in [
        mk("default", "alltoall", alltoall_default, None, _nb0,
           desc="lax.all_to_all"),
        mk("alltoall_as_alltoallv", "alltoall", alltoall_as_alltoallv,
           "GL8", lambda n, p: 2 * p * _I),
        mk("alltoall_as_ppermute", "alltoall", alltoall_as_ppermute,
           "EXT", lambda n, p: n),
    ]}

    r["bcast"] = {i.name: i for i in [
        mk("default", "bcast", bcast_as_psum, None, _nb0,
           desc="select + all-reduce (XLA canonical)"),
        mk("bcast_as_allgatherv", "bcast", bcast_as_allgatherv,
           "GL9", lambda n, p: 2 * p * _I + n),
        mk("bcast_as_scatter_allgather", "bcast", bcast_as_scatter_allgather,
           "GL10", lambda n, p: (n + p) + (n + p) // p,
           desc="van de Geijn"),
        mk("bcast_as_tree", "bcast", bcast_as_tree, "EXT", _nb0,
           desc="binomial tree"),
    ]}

    r["gather"] = {i.name: i for i in [
        mk("default", "gather", gather_as_allgather, None,
           lambda n, p: p * n, desc="all_gather; non-roots superset"),
        mk("gather_as_allgather", "gather", gather_as_allgather,
           "GL11", lambda n, p: p * n),
        mk("gather_as_gatherv", "gather", gather_as_gatherv,
           "GL12", lambda n, p: 2 * p * _I),
        mk("gather_as_reduce", "gather", gather_as_reduce,
           "GL13", lambda n, p: p * n, desc="one-hot + reduce"),
        mk("gather_as_tree", "gather", gather_as_tree,
           "EXT", lambda n, p: p * n),
    ]}

    r["reduce"] = {i.name: i for i in [
        mk("default", "reduce", reduce_as_allreduce, None,
           lambda n, p: n, desc="psum; non-roots superset"),
        mk("reduce_as_allreduce", "reduce", reduce_as_allreduce,
           "GL14", lambda n, p: n),
        mk("reduce_as_rsb_gather", "reduce", reduce_as_rsb_gather,
           "GL15", lambda n, p: (n + p) + (n + p) // p),
        mk("reduce_as_rs_gatherv", "reduce", reduce_as_rs_gatherv,
           "GL16", lambda n, p: max(n // p + 1, 1) + 2 * p * _I),
        mk("reduce_as_tree", "reduce", reduce_as_tree, "EXT", _nb0),
    ]}

    r["reducescatter"] = {i.name: i for i in [
        mk("default", "reducescatter", reducescatter_default, None, _nb0,
           desc="lax.psum_scatter"),
        mk("rsb_as_reduce_scatter", "reducescatter", rsb_as_reduce_scatter,
           "GL17", lambda n, p: n, desc="reduce + scatter"),
        mk("rsb_as_reduce_scatter_irr", "reducescatter",
           rsb_as_reduce_scatter_irr, "GL18", lambda n, p: p * _I),
        mk("rsb_as_allreduce", "reducescatter", rsb_as_allreduce,
           "GL19", lambda n, p: n),
        mk("MPIX_rs_rs", "reducescatter", reducescatter_hier, "EXT",
           lambda n, p: 2 * max(n // p, 1), hier=True,
           desc="hierarchical RS-inter -> RS-intra (MPIX_ag_ag dual): "
                "slow tier reduces node blocks, fast tier finishes"),
        *mk_wire("reducescatter", reducescatter_wire,
                 lambda n, p: 2 * max(n // p, 1),
                 desc="ring with the travelling accumulator requantized "
                      "per hop (f32 accumulate)"),
    ]}

    r["scan"] = {i.name: i for i in [
        mk("default", "scan", scan_default, None, _nb0,
           desc="Hillis-Steele over ppermute"),
        mk("scan_as_exscan_reducelocal", "scan", scan_as_exscan_reducelocal,
           "GL20", _nb0),
    ]}

    r["exscan"] = {i.name: i for i in [
        mk("default", "exscan", exscan_default, None, _nb0),
    ]}

    r["allgather_matmul"] = {i.name: i for i in [
        mk("default", "allgather_matmul", allgather_matmul_default, None,
           lambda n, p: p * n, desc="all_gather then dense matmul (unfused)"),
        mk("fused_ring", "allgather_matmul", allgather_matmul_fused_ring,
           "EXT", lambda n, p: p * n + 2 * n,
           desc="ring overlap: chunk matmul while next chunk in flight"),
        *mk_wire("allgather_matmul", allgather_matmul_wire,
                 lambda n, p: p * n + 2 * n + n // 2,
                 desc="fused ring, activation chunk on the 8-bit wire"),
    ]}

    r["matmul_reducescatter"] = {i.name: i for i in [
        mk("default", "matmul_reducescatter", matmul_reducescatter_default,
           None, lambda n, p: n, desc="dense matmul then psum_scatter"),
        mk("fused_ring", "matmul_reducescatter",
           matmul_reducescatter_fused_ring, "EXT",
           lambda n, p: 2 * max(n // p, 1),
           desc="ring overlap: travelling accumulator hides matmul"),
        *mk_wire("matmul_reducescatter", matmul_reducescatter_wire,
                 lambda n, p: 2 * max(n // p, 1),
                 desc="fused ring, partial-product accumulator on the "
                      "8-bit wire (requantized per hop)"),
    ]}

    r["matmul_accumulate"] = {i.name: i for i in [
        mk("default", "matmul_accumulate", matmul_accumulate_default, None,
           lambda n, p: p * n,
           desc="all_gather K-dim weight then dense matmul (unfused)"),
        mk("fused_ring", "matmul_accumulate", matmul_accumulate_fused_ring,
           "EXT", lambda n, p: p * n + 2 * n,
           desc="ring overlap: weight block in flight while partials "
                "accumulate"),
        *mk_wire("matmul_accumulate", matmul_accumulate_wire,
                 lambda n, p: p * n + 2 * n + n // 2,
                 desc="fused ring, weight block on the 8-bit wire "
                      "(quantized once at origin)"),
    ]}

    r["matmul_reducescatter_2d"] = {i.name: i for i in [
        mk("default", "matmul_reducescatter_2d",
           matmul_reducescatter_2d_default, None,
           lambda n, p: p * n,
           desc="all_gather weight cols then dense matmul then psum_scatter"
                " (unfused 2-D composition)"),
        mk("fused_ring2d", "matmul_reducescatter_2d",
           matmul_reducescatter_2d_fused_ring, "EXT",
           lambda n, p: p * n + 2 * n,
           desc="nested rings: outer weight stream over the gather axis, "
                "inner matmul-reducescatter over the scatter axis"),
    ]}

    r["scatter"] = {i.name: i for i in [
        mk("default", "scatter", scatter_as_alltoall, None, _nb0,
           desc="masked all_to_all + segment select"),
        mk("scatter_as_bcast", "scatter", scatter_as_bcast,
           "GL21", lambda n, p: n, desc="bcast + local slice"),
        mk("scatter_as_scatterv", "scatter", scatter_as_scatterv,
           "GL22", lambda n, p: 2 * p * _I),
        mk("scatter_as_tree", "scatter", scatter_as_tree,
           "EXT", _nb0, pow2=True),
    ]}

    return r


REGISTRY: dict[str, dict[str, Impl]] = _reg()

OPS = tuple(REGISTRY.keys())

# ---------------------------------------------------------------------------
# demotion ledger: impls removed from the admissible set at runtime.
# A quantized-wire impl whose numeric error exceeds its wire tolerance on a
# representative payload (core/selfcheck.py) is demoted here and from then on
# is treated exactly like a failed guideline: api._select falls back to the
# default, api._admissible_impls / tuner skip it, plan vectors never carry
# it.  Process-local state, keyed (op, impl name).
# ---------------------------------------------------------------------------

_DEMOTED: dict[tuple[str, str], str] = {}


def demote(op: str, name: str, reason: str = "tolerance") -> None:
    """Remove ``(op, name)`` from the admissible set for this process."""
    if name == "default":
        raise ValueError("the default impl cannot be demoted")
    if name not in REGISTRY[op]:
        raise KeyError(f"unknown impl {op}.{name}")
    _DEMOTED[(op, name)] = reason


def is_demoted(op: str, name: str) -> bool:
    return (op, name) in _DEMOTED


def demotions() -> dict[tuple[str, str], str]:
    """Snapshot of the current demotion ledger (copy)."""
    return dict(_DEMOTED)


def clear_demotions() -> None:
    _DEMOTED.clear()


def get_impl(op: str, name: str | None = None) -> Impl:
    table = REGISTRY[op]
    return table[name or "default"]


def impl_names(op: str, *, include_default: bool = True) -> list[str]:
    names = list(REGISTRY[op].keys())
    if not include_default:
        names = [n for n in names if n != "default"]
    return names
