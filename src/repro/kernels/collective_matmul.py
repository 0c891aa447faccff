"""Latency-hiding collective-matmul kernels (ring schedules, Pallas + ref).

Four fused primitives, each semantically equal to an unfused collective
composition around a dense matmul:

* ``ring_allgather_matmul``          out = all_gather(x, rows) @ w
* ``ring_matmul_reducescatter``      out = reduce_scatter(x @ w, rows)
* ``ring_matmul_accumulate``         out = x @ all_gather(w, rows)
* ``ring_matmul_reducescatter_2d``   out = reduce_scatter(
                                         x @ all_gather(w, cols, ag_axis),
                                         rows, rs_axis)   — TWO mesh axes

All run the classic (p-1)-step neighbour ring, but matmul the chunk they
already hold while the next chunk is in flight — the "collective matmul" of
Wang et al. (overlap of ICI transfers with MXU work), applied here as a
tunable mock-up: the dispatcher's ``fused_ring`` / ``fused_ring2d`` impls
of the ``allgather_matmul`` / ``matmul_reducescatter`` /
``matmul_accumulate`` / ``matmul_reducescatter_2d`` ops
(core/collectives.py) call these, and the tuner arbitrates fused vs unfused
per tuning cell exactly like any other guideline.

The ring schedules differ in WHAT travels and WHAT stays resident:

=========================  ==================  ===========================
schedule                   travelling operand  per-step local work
=========================  ==================  ===========================
allgather-matmul           activation chunk    chunk row-block @ resident w
                           (gather role)       -> disjoint output rows
matmul-reducescatter       output accumulator  resident x row-block @ w,
                           (scatter role)      added into the accumulator
matmul-accumulate          weight block        x K-slice @ weight block,
                           (contract role)     accumulated into [T, M] out
matmul-reducescatter-2d    outer: weight       inner matmul-reducescatter
                           column block over   ring over ``rs_axis`` of the
                           ``ag_axis``; inner: resident x against the
                           output accumulator  resident weight block —
                           over ``rs_axis``    nested rings, issue-before-
                                               consume on BOTH axes
=========================  ==================  ===========================

The 2-D schedule is weight-stationary in the serving sense: each rank's
FSDP weight shard never leaves its ring slot's rotation — one column block
is in flight on the outer (data) ring while the previous block's partial
products are being reduce-scattered over the inner (model) ring.
``ring_matmul_reducescatter_2d_t`` is its transpose (the dw schedule of
the paired VJP): the gathered operand's dim is CONTRACTED away (outer
travelling accumulator over the scatter axis, inner contract-stream of the
cotangent's column slice over the gather axis).

Three execution tiers:

1. **Reference ring** (any backend, incl. CPU CI): ``lax.ppermute`` steps
   with a per-chunk local matmul.  The permute for chunk s+1 is issued
   *before* chunk s is consumed, so the dataflow graph exposes the overlap
   to XLA's latency-hiding scheduler; per-row contraction order matches the
   unfused composition, so the all-gather direction is bit-exact.
2. **Pallas block matmul** (``pallas_matmul``): the per-chunk matmul as a
   tiled MXU kernel with an fp32 VMEM accumulator; used inside the ring on
   TPU and exercised on CPU via ``interpret=True``.
3. **RDMA ring kernel** (``collective_matmul_rdma.ring_allgather_matmul_
   rdma``): a single Pallas kernel that drives ``make_async_remote_copy``
   sends itself — the full latency-hiding schedule with no XLA scheduling
   dependence.  TPU-only, kept in its own module and NOT on the dispatch
   path: ``fused_ring`` is the tier-1 ring (with the tier-2 chunk matmul on
   TPU) on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core._axis import axis_index, axis_size, ring_perm

__all__ = ["pallas_matmul", "ring_allgather_matmul",
           "ring_matmul_reducescatter", "ring_matmul_accumulate",
           "ring_matmul_reducescatter_2d", "ring_matmul_reducescatter_2d_t",
           "ring_allgather_matmul_wire", "ring_matmul_reducescatter_wire",
           "ring_matmul_accumulate_wire", "on_tpu"]


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def on_tpu() -> bool:
    """Backend check gating the compiled (non-interpret) Pallas tier."""
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover - backend probing never fatal
        return False


_on_tpu = on_tpu  # internal alias


# ---------------------------------------------------------------------------
# tier 2: tiled local matmul (the per-chunk compute of the ring)
# ---------------------------------------------------------------------------


def _mm_kernel(x_ref, w_ref, o_ref, acc_scr, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finish():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def pallas_matmul(x, w, *, bm: int = 128, bn: int = 128, bk: int = 128,
                  interpret: bool = False):
    """``x @ w`` as a tiled Pallas kernel (fp32 accumulation).

    Non-divisible shapes are zero-padded up to the block grid and the
    result sliced back — rows/cols of the pad contribute nothing.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    mp, np_, kp = _cdiv(m, bm) * bm, _cdiv(n, bn) * bn, _cdiv(k, bk) * bk
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k))) if (mp, kp) != (m, k) else x
    wp = jnp.pad(w, ((0, kp - k), (0, np_ - n))) if (kp, np_) != (k, n) else w
    nk = kp // bk
    out = pl.pallas_call(
        functools.partial(_mm_kernel, nk=nk),
        grid=(mp // bm, np_ // bn, nk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, wp)
    return out[:m, :n]


def _local_mm(x, w, mm: str):
    """The per-chunk matmul: 'jnp' reference, 'pallas' MXU kernel, or
    'auto' (pallas on TPU, jnp elsewhere — CPU CI stays on the exact
    jnp contraction so the fused ring is bit-comparable to unfused)."""
    if mm == "auto":
        mm = "pallas" if _on_tpu() else "jnp"
    if mm == "pallas":
        return pallas_matmul(x, w, interpret=not _on_tpu())
    return jnp.matmul(x, w)


# ---------------------------------------------------------------------------
# tier 1: reference rings (ppermute chunks + per-chunk matmul)
# ---------------------------------------------------------------------------


def ring_allgather_matmul(x, w, axis: str, *, return_gathered: bool = False,
                          mm: str = "auto"):
    """``all_gather(x, rows) @ w`` with per-chunk overlap.

    x: per-shard ``[n, K]`` (rows gathered over ``axis``), w: ``[K, M]``
    (shard-local) -> ``[p*n, M]``.  Step s matmuls the chunk originated by
    rank ``idx - s`` while the ppermute moving chunk s+1 is already in
    flight.  Row results use the exact same K-contraction as the unfused
    ``matmul(all_gather(x), w)`` — bit-identical per row for ``mm='jnp'``.

    ``return_gathered=True`` additionally returns the assembled
    ``all_gather(x)`` — the ring materializes it for free, and custom VJPs
    reuse it instead of re-gathering.
    """
    p = axis_size(axis)
    n = x.shape[0]
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    if p == 1:
        out = _local_mm(x, w, mm).astype(out_dtype)
        return (out, x) if return_gathered else out
    idx = axis_index(axis)
    zeros = (0,) * (x.ndim - 1)
    out = jnp.zeros((p * n, w.shape[-1]), out_dtype)
    gath = jnp.zeros((p * n,) + x.shape[1:], x.dtype) if return_gathered \
        else None
    cur = x
    for s in range(p):
        # issue the transfer of the NEXT chunk before consuming this one:
        # the matmul below has no data dependence on it, so the scheduler
        # can run both concurrently.
        nxt = lax.ppermute(cur, axis, ring_perm(p, 1)) if s < p - 1 else None
        src = (idx - s) % p                # originating rank of `cur`
        blk = _local_mm(cur, w, mm).astype(out_dtype)
        out = lax.dynamic_update_slice(out, blk, (src * n, 0))
        if return_gathered:
            gath = lax.dynamic_update_slice(gath, cur, (src * n,) + zeros)
        cur = nxt
    return (out, gath) if return_gathered else out


def ring_matmul_reducescatter(x, w, axis: str, *, mm: str = "auto"):
    """``reduce_scatter(x @ w, rows)`` with per-chunk overlap.

    x: per-shard ``[p*n, K]`` (partial contraction — different shards hold
    different K-slices of the logical operand), w: ``[K, M]`` ->
    ``[n, M]`` summed over ``axis``.  The travelling accumulator picks up
    rank j's contribution to row-block b at step ``b = (j + p-1-s) % p``;
    while it is in flight the next step's local contribution (a pure
    function of resident x, w) can already be computed.
    """
    p = axis_size(axis)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    if p == 1:
        return _local_mm(x, w, mm).astype(out_dtype)
    rows = x.shape[0]
    assert rows % p == 0, f"rows {rows} not divisible by axis size {p}"
    n = rows // p
    idx = axis_index(axis)
    acc = None
    for s in range(p):
        blk_id = (idx + (p - 1 - s)) % p
        blk = lax.dynamic_slice(x, (blk_id * n,) + (0,) * (x.ndim - 1),
                                (n,) + x.shape[1:])
        contrib = _local_mm(blk, w, mm).astype(out_dtype)
        acc = contrib if acc is None else acc + contrib
        if s < p - 1:
            acc = lax.ppermute(acc, axis, ring_perm(p, 1))
    return acc


def ring_matmul_accumulate(x, w, axis: str, *, return_gathered: bool = False,
                           mm: str = "auto"):
    """``x @ all_gather(w, rows)`` with per-block overlap — the contraction-
    dim ring.

    x: ``[T, K]`` shard-local (K = p·k_loc, the full contraction), w:
    per-shard ``[k_loc, M]`` (rows gathered over ``axis``) -> ``[T, M]``.
    The gathered dim is contracted away, so neither row-block schedule
    applies; instead the WEIGHT blocks travel: step s matmuls the K-slice of
    ``x`` matching the block originated by rank ``idx - s`` into a local
    accumulator while the ppermute moving block s+1 is already in flight
    (issue-before-consume, same overlap law as the other rings).

    ``return_gathered=True`` additionally returns the assembled
    ``all_gather(w)`` — the ring materializes it for free, and custom VJPs
    reuse it for the input gradient instead of re-gathering.
    """
    p = axis_size(axis)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    if p == 1:
        out = _local_mm(x, w, mm).astype(out_dtype)
        return (out, w) if return_gathered else out
    k_loc = w.shape[0]
    assert x.shape[-1] == p * k_loc, (x.shape, w.shape, p)
    idx = axis_index(axis)
    zeros = (0,) * (w.ndim - 1)
    gath = jnp.zeros((p * k_loc,) + w.shape[1:], w.dtype) if return_gathered \
        else None
    acc = None
    cur = w
    for s in range(p):
        # issue the transfer of the NEXT weight block before consuming this
        # one — the accumulate below has no data dependence on it
        nxt = lax.ppermute(cur, axis, ring_perm(p, 1)) if s < p - 1 else None
        src = (idx - s) % p                # originating rank of `cur`
        xblk = lax.dynamic_slice_in_dim(x, src * k_loc, k_loc, axis=-1)
        contrib = _local_mm(xblk, cur, mm).astype(out_dtype)
        acc = contrib if acc is None else acc + contrib
        if return_gathered:
            gath = lax.dynamic_update_slice(gath, cur, (src * k_loc,) + zeros)
        cur = nxt
    return (acc, gath) if return_gathered else acc


# ---------------------------------------------------------------------------
# tier 1b: the weight-stationary 2-D nested ring (data × model)
# ---------------------------------------------------------------------------


def ring_matmul_reducescatter_2d(x, w, rs_axis: str, ag_axis: str, *,
                                 return_gathered: bool = False,
                                 mm: str = "auto"):
    """``reduce_scatter(x @ all_gather(w, cols over ag_axis), rows over
    rs_axis)`` with nested overlap — the weight-stationary 2-D collective
    matmul.

    x: ``[T, K]`` shard-local (T divisible by ``q = size(rs_axis)``), w:
    per-shard ``[K, m_loc]`` (column block of the logical ``[K, d·m_loc]``
    weight, gathered over ``d = size(ag_axis)``) -> ``[T/q, d·m_loc]``
    summed over ``rs_axis`` with row-block i landing on inner-rank i.

    Nested rings: the OUTER ring streams weight column blocks over
    ``ag_axis`` (d steps, issue-before-consume — the ppermute moving block
    s+1 is issued before block s is consumed); each outer step runs a full
    INNER ``ring_matmul_reducescatter`` over ``rs_axis`` (itself
    issue-before-consume), whose ``[T/q, m_loc]`` result fills the outer
    block's output columns.  Both transfers overlap MXU work, so the
    modeled cost is ``max(outer_comm, per-step max(inner_comm, compute))``
    per outer step (costmodel.t_overlapped_ring2d).

    ``return_gathered=True`` additionally returns the assembled
    ``all_gather(w, cols)`` ``[K, d·m_loc]`` — the outer ring materializes
    it for free, and the paired VJP reuses it for dx instead of
    re-gathering.
    """
    d = axis_size(ag_axis)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    if d == 1:
        out = ring_matmul_reducescatter(x, w, rs_axis, mm=mm)
        return (out, w) if return_gathered else out
    m_loc = w.shape[-1]
    idx = axis_index(ag_axis)
    q = axis_size(rs_axis)
    rows = x.shape[0]
    assert rows % q == 0, f"rows {rows} not divisible by rs axis size {q}"
    out = jnp.zeros((rows // q, d * m_loc), out_dtype)
    gath = jnp.zeros((w.shape[0], d * m_loc), w.dtype) if return_gathered \
        else None
    cur = w
    for s in range(d):
        # issue the transfer of the NEXT weight block before consuming this
        # one — the inner ring below has no data dependence on it
        nxt = lax.ppermute(cur, ag_axis, ring_perm(d, 1)) if s < d - 1 \
            else None
        src = (idx - s) % d                # originating rank of `cur`
        blk = ring_matmul_reducescatter(x, cur, rs_axis, mm=mm)
        out = lax.dynamic_update_slice(out, blk.astype(out_dtype),
                                       (0, src * m_loc))
        if return_gathered:
            gath = lax.dynamic_update_slice(gath, cur, (0, src * m_loc))
        cur = nxt
    return (out, gath) if return_gathered else out


def ring_matmul_reducescatter_2d_t(g, x, rs_axis: str, ag_axis: str, *,
                                   mm: str = "auto"):
    """``reduce_scatter(all_gather(g, rows over ag_axis)ᵀ @ x, rows over
    rs_axis)`` — the TRANSPOSE 2-D schedule (the dw of the paired VJP).

    g: per-shard ``[t_loc, M]`` (row block of the logical ``[q·t_loc, M]``
    cotangent, gathered over ``q = size(ag_axis)``), x: ``[q·t_loc, K]``
    shard-local -> ``[M/d, K]`` summed over ``rs_axis``
    (``d = size(rs_axis)``; M divisible by d).

    Relative to the forward 2-D schedule both axes swap roles AND the
    gathered dim is CONTRACTED away (like ``matmul_accumulate`` vs the
    row-block rings): the OUTER ring is the travelling output accumulator
    over ``rs_axis``; per outer step the needed ``[t_loc, M/d]`` COLUMN
    SLICE of the cotangent streams around ``ag_axis`` (inner ring,
    issue-before-consume), so the full cotangent crosses the gather axis
    exactly once in total.
    """
    d = axis_size(rs_axis)
    out_dtype = jnp.result_type(g.dtype, x.dtype)
    q = axis_size(ag_axis)
    M = g.shape[-1]
    assert M % d == 0, f"cols {M} not divisible by rs axis size {d}"
    m_loc = M // d
    t_loc = g.shape[0]
    assert x.shape[0] == q * t_loc, (g.shape, x.shape, q)
    idx_rs = axis_index(rs_axis)
    idx_ag = axis_index(ag_axis)
    acc = None
    for s in range(d):
        # travelling-accumulator target of this outer step (same block
        # order as ring_matmul_reducescatter)
        blk_id = (idx_rs + (d - 1 - s)) % d
        cur = lax.dynamic_slice(g, (0, blk_id * m_loc), (t_loc, m_loc))
        contrib = None
        for t in range(q):
            # inner contract-stream: cotangent slice t+1 in flight while
            # slice t multiplies its matching x row block
            nxt = lax.ppermute(cur, ag_axis, ring_perm(q, 1)) \
                if t < q - 1 else None
            src = (idx_ag - t) % q         # originating rank of `cur`
            xblk = lax.dynamic_slice_in_dim(x, src * t_loc, t_loc, axis=0)
            c = _local_mm(jnp.swapaxes(cur, 0, 1), xblk, mm).astype(out_dtype)
            contrib = c if contrib is None else contrib + c
            cur = nxt
        acc = contrib if acc is None else acc + contrib
        if s < d - 1:
            acc = lax.ppermute(acc, rs_axis, ring_perm(d, 1))
    return acc


# ---------------------------------------------------------------------------
# tier 1c: quantized-wire rings (wire_q8 / wire_fp8 mock-up families)
#
# Same (p-1)-step issue-before-consume schedules as the f32 rings above, but
# the TRAVELLING operand crosses the wire in an 8-bit format with per-block
# scales (kernels/quant.py).  Two regimes:
#
# * gather-style (allgather-matmul, accumulate): the payload is quantized
#   ONCE at its origin and the (values, scales) pair travels unchanged —
#   every receiver dequantizes the same single-roundtrip approximation, and
#   the resident chunk (which never crossed the wire) stays exact.
# * travelling accumulator (matmul-reducescatter): the accumulator must be
#   requantized before every hop; dequantized contributions are ALWAYS
#   summed in f32 (the accumulate-in-f32 rule the selfcheck tolerance gate
#   assumes), so errors add per hop but never compound multiplicatively.
# ---------------------------------------------------------------------------


def ring_allgather_matmul_wire(x, w, axis: str, *, wire_dtype: str = "int8",
                               return_gathered: bool = False,
                               mm: str = "auto"):
    """``ring_allgather_matmul`` with the travelling activation chunk sent
    as (8-bit values, per-block scales); dequantize-on-receive feeds the
    per-chunk matmul.  ``return_gathered`` returns the wire-approximate
    gathered operand (own chunk exact)."""
    from repro.kernels import quant as Qz
    p = axis_size(axis)
    n = x.shape[0]
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    if p == 1:
        out = _local_mm(x, w, mm).astype(out_dtype)
        return (out, x) if return_gathered else out
    idx = axis_index(axis)
    zeros = (0,) * (x.ndim - 1)
    out = jnp.zeros((p * n, w.shape[-1]), out_dtype)
    gath = jnp.zeros((p * n,) + x.shape[1:], x.dtype) if return_gathered \
        else None
    q, sc = Qz.quantize(x, wire_dtype)
    cur = x                                 # resident chunk: never on the wire
    for s in range(p):
        # issue the transfer of the NEXT chunk's wire pair before consuming
        # this one (same overlap exposure as the f32 ring)
        nxt = (lax.ppermute(q, axis, ring_perm(p, 1)),
               lax.ppermute(sc, axis, ring_perm(p, 1))) if s < p - 1 else None
        src = (idx - s) % p                 # originating rank of `cur`
        blk = _local_mm(cur, w, mm).astype(out_dtype)
        out = lax.dynamic_update_slice(out, blk, (src * n, 0))
        if return_gathered:
            gath = lax.dynamic_update_slice(gath, cur.astype(x.dtype),
                                            (src * n,) + zeros)
        if nxt is not None:
            q, sc = nxt
            cur = Qz.dequantize(q, sc, x.dtype)
    return (out, gath) if return_gathered else out


def ring_matmul_reducescatter_wire(x, w, axis: str, *,
                                   wire_dtype: str = "int8",
                                   mm: str = "auto"):
    """``ring_matmul_reducescatter`` with the travelling accumulator
    requantized per hop; contributions accumulate in f32 after dequant."""
    from repro.kernels import quant as Qz
    p = axis_size(axis)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    if p == 1:
        return _local_mm(x, w, mm).astype(out_dtype)
    rows = x.shape[0]
    assert rows % p == 0, f"rows {rows} not divisible by axis size {p}"
    n = rows // p
    idx = axis_index(axis)
    acc = None
    for s in range(p):
        blk_id = (idx + (p - 1 - s)) % p
        blk = lax.dynamic_slice(x, (blk_id * n,) + (0,) * (x.ndim - 1),
                                (n,) + x.shape[1:])
        contrib = _local_mm(blk, w, mm).astype(jnp.float32)
        acc = contrib if acc is None else acc + contrib
        if s < p - 1:
            q, sc = Qz.quantize(acc, wire_dtype)
            q = lax.ppermute(q, axis, ring_perm(p, 1))
            sc = lax.ppermute(sc, axis, ring_perm(p, 1))
            acc = Qz.dequantize(q, sc, jnp.float32)
    return acc.astype(out_dtype)


def ring_matmul_accumulate_wire(x, w, axis: str, *, wire_dtype: str = "int8",
                                return_gathered: bool = False,
                                mm: str = "auto"):
    """``ring_matmul_accumulate`` with the travelling weight block sent as
    a wire pair quantized once at its origin; partial products accumulate
    in f32 after dequant."""
    from repro.kernels import quant as Qz
    p = axis_size(axis)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    if p == 1:
        out = _local_mm(x, w, mm).astype(out_dtype)
        return (out, w) if return_gathered else out
    k_loc = w.shape[0]
    assert x.shape[-1] == p * k_loc, (x.shape, w.shape, p)
    idx = axis_index(axis)
    zeros = (0,) * (w.ndim - 1)
    gath = jnp.zeros((p * k_loc,) + w.shape[1:], w.dtype) if return_gathered \
        else None
    q, sc = Qz.quantize(w, wire_dtype)
    cur = w                                 # resident block: never on the wire
    acc = None
    for s in range(p):
        nxt = (lax.ppermute(q, axis, ring_perm(p, 1)),
               lax.ppermute(sc, axis, ring_perm(p, 1))) if s < p - 1 else None
        src = (idx - s) % p                 # originating rank of `cur`
        xblk = lax.dynamic_slice_in_dim(x, src * k_loc, k_loc, axis=-1)
        contrib = _local_mm(xblk, cur, mm).astype(jnp.float32)
        acc = contrib if acc is None else acc + contrib
        if return_gathered:
            gath = lax.dynamic_update_slice(gath, cur.astype(w.dtype),
                                            (src * k_loc,) + zeros)
        if nxt is not None:
            q, sc = nxt
            cur = Qz.dequantize(q, sc, w.dtype)
    out = acc.astype(out_dtype)
    return (out, gath) if return_gathered else out
