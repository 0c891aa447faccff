"""Sharded model-parallel primitives — the "MPI application code" layer.

These are the ops the model stack (models/, train/) calls.  Every forward
AND backward collective is issued through ``repro.core.api``, never raw
``jax.lax`` — so an active ``api.tuned(profiles=..., force=...)`` context or
a ``PGTUNE_MODULE`` env spec transparently redirects training and serving
traffic to guideline mock-ups, exactly as PGMPITuneLib intercepts ``MPI_*``
into tuned ``PMPI_*`` compositions.  Because the custom VJPs below route the
backward collective through the same dispatcher, the tuner's per-(op, p,
message-size) choices apply to the backward pass too.

Gradient pairing (per-shard semantics; axis size ``p``):

===================  =========================  ==========================
op                   forward collective         backward collective
===================  =========================  ==========================
fsdp_gather          api.allgather (data)       api.reducescatter (data)
tp_allgather_split   api.allgather (model)      own block (Megatron gather)
tp_reducescatter     api.reducescatter          api.allgather
tp_allreduce         api.allreduce              identity (Megatron "g")
tp_copy              identity                   api.allreduce (Megatron "f")
tp_psum_grad         identity                   api.allreduce (weight marker)
ep_alltoall          api.alltoall               api.alltoall (self-transpose)
row_matmul           api.allreduce              identity
col_matmul           identity                   api.matmul_reducescatter +
                                                api.allgather (input grad)
allgather_matmul     api.allgather_matmul       api.matmul_reducescatter (dx)
                                                + api.allgather (dw remat)
matmul_reducescatter api.matmul_reducescatter   api.allgather_matmul (dx; the
                                                gathered cotangent is reused
                                                for dw)
fsdp_matmul          api.allgather_matmul       api.matmul_reducescatter (dw)
                     (data — weight gather      — the FSDP grad
                     fused into the matmul)     reduce-scatter, fused
matmul_accumulate    api.matmul_accumulate      api.matmul_reducescatter (dw
                     (data — K-dim weight       reduce-scatter over K rows);
                     gather, CONTRACTED away)   dx reuses the gathered weight
matmul_reducescatter api.matmul_reducescatter   api.allgather_matmul (dx) +
_2d                  _2d (data-gather AND       api.matmul_reducescatter_2d_t
                     model-reduce-scatter       (dw — the fused 2-D
                     fused around one matmul)   TRANSPOSE schedule: axes
                                                swap roles)
===================  =========================  ==========================

The fused pair (``allgather_matmul`` / ``matmul_reducescatter``) exposes the
collective-matmul overlap to the tuner: the dispatcher chooses between the
unfused composition and the ring ``fused_ring`` kernel per (op, p, nbytes).
``col_matmul``'s input-grad all-reduce is decomposed as reduce-scatter +
all-gather so its matmul-reduce-scatter half is fused-selectable (falls back
to the single all-reduce when the row count does not divide the axis);
``row_matmul(..., fsdp_dim=1)`` fuses the DATA-axis weight gather of a
row-parallel weight into the matmul itself (the fsdp_gather→matmul sites in
models/), keeping the model-axis reduction a classic tunable all-reduce.

``tp_allgather_split`` is for a gathered value that every shard then uses
alike: its cotangent is the same on every shard, so each keeps its own
block and the backward needs no collective.

``tp_copy`` marks a replicated ACTIVATION entering a model-sharded region
(its cotangents arrive partial per shard and must be summed);
``tp_psum_grad`` marks a replicated WEIGHT used by every shard (partial
weight grads must be summed before the optimizer).  Identical math, distinct
ops so dispatch records and profiles stay attributable.

When the named axis is NOT bound in the current trace every op degrades to
identity / a local matmul: single-device ``jit`` runs the exact same model
code unsharded.

Every backward collective is issued under ``api.phase("bwd")``, so dispatch
records (and the trace-replay tuner's per-phase profiles — see
DESIGN_TRACE.md) distinguish forward from backward traffic.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import api
from repro.core._axis import axis_index, axis_size, tie_to_axis
from repro.dist.axes import AXES, has_axis


def _moved(fn, x, dim: int):
    """Apply a leading-dim collective along ``dim``."""
    if dim in (0, -x.ndim):
        return fn(x)
    return jnp.moveaxis(fn(jnp.moveaxis(x, dim, 0)), 0, dim)


# ---------------------------------------------------------------------------
# allgather <-> reducescatter pair
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _gather(dim: int, axis: str, x):
    return _moved(lambda a: api.allgather(a, axis), x, dim)


def _gather_fwd(dim, axis, x):
    return _gather(dim, axis, x), None


def _gather_bwd(dim, axis, _, g):
    with api.phase("bwd"):
        return (_moved(lambda a: api.reducescatter(a, axis), g, dim),)


_gather.defvjp(_gather_fwd, _gather_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scatter(dim: int, axis: str, x):
    return _moved(lambda a: api.reducescatter(a, axis), x, dim)


def _scatter_fwd(dim, axis, x):
    return _scatter(dim, axis, x), None


def _scatter_bwd(dim, axis, _, g):
    with api.phase("bwd"):
        return (_moved(lambda a: api.allgather(a, axis), g, dim),)


_scatter.defvjp(_scatter_fwd, _scatter_bwd)


def fsdp_gather(x, dim: int = 0, axis: str = AXES.data):
    """All-gather a ZeRO-3-sharded param along ``dim`` over the data axis;
    the backward reduce-scatters the grad back to the owner shard (summed
    over the axis — see train/trainer.py for the /d normalization)."""
    if not has_axis(axis):
        return x
    return _gather(dim, axis, x)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _gather_split(dim: int, axis: str, x):
    return _moved(lambda a: api.allgather(a, axis), x, dim)


def _gather_split_fwd(dim, axis, x):
    return _gather_split(dim, axis, x), None


def _gather_split_bwd(dim, axis, _, g):
    n = g.shape[dim] // axis_size(axis)
    return (jax.lax.dynamic_slice_in_dim(g, axis_index(axis) * n, n, dim),)


_gather_split.defvjp(_gather_split_fwd, _gather_split_bwd)


def tp_allgather_split(x, dim: int, axis: str = AXES.model):
    """All-gather a model-sharded activation that every shard then uses
    alike; the backward keeps this shard's block of the cotangent, which
    is the same on every shard, with no collective."""
    if not has_axis(axis):
        return x
    return _gather_split(dim % x.ndim, axis, x)


def tp_reducescatter(x, dim: int = 0, axis: str = AXES.model):
    """Reduce-scatter along ``dim`` over the model axis (sum + keep own
    block); backward all-gathers the cotangent."""
    if not has_axis(axis):
        return x
    return _scatter(dim, axis, x)


# ---------------------------------------------------------------------------
# allreduce <-> identity pair (Megatron f/g)
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _allreduce(axis: str, x):
    return api.allreduce(x, axis)


def _allreduce_fwd(axis, x):
    return _allreduce(axis, x), None


def _allreduce_bwd(axis, _, g):
    # the reduced value is ONE logical tensor replicated over the axis; its
    # (replicated) cotangent passes through untouched
    return (g,)


_allreduce.defvjp(_allreduce_fwd, _allreduce_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _psum_grad(axis: str, x):
    return x


def _psum_grad_fwd(axis, x):
    return x, None


def _psum_grad_bwd(axis, _, g):
    with api.phase("bwd"):
        return (api.allreduce(g, axis),)


_psum_grad.defvjp(_psum_grad_fwd, _psum_grad_bwd)


def tp_allreduce(x, axis: str = AXES.model):
    """Sum partial activations over the model axis (row-parallel output)."""
    if not has_axis(axis):
        return x
    return _allreduce(axis, x)


def tp_copy(x, axis: str = AXES.model):
    """Mark a replicated activation entering a model-sharded region: fwd is
    identity, bwd sums the per-shard partial cotangents."""
    if not has_axis(axis):
        return x
    return _psum_grad(axis, x)


def tp_psum_grad(x, axis: str = AXES.model):
    """Mark a replicated weight used on every model shard: fwd identity,
    bwd sums the partial weight grads over the axis."""
    if not has_axis(axis):
        return x
    return _psum_grad(axis, x)


# ---------------------------------------------------------------------------
# alltoall (expert parallelism)
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _alltoall(axis: str, x):
    return api.alltoall(x, axis)


def _alltoall_fwd(axis, x):
    return _alltoall(axis, x), None


def _alltoall_bwd(axis, _, g):
    # y_i[j] = x_j[i] is its own transpose: route the cotangent back through
    # the (tuned) alltoall; tie_to_axis keeps old-jax vmap batching honest
    with api.phase("bwd"):
        return (api.alltoall(tie_to_axis(g, axis), axis),)


_alltoall.defvjp(_alltoall_fwd, _alltoall_bwd)


def ep_alltoall(x, axis: str = AXES.model):
    """Expert dispatch/combine shuffle: rows [p*n, ...] exchanged so shard i
    receives block i of every peer.  Self-inverse; backward is the same
    (tuned) alltoall."""
    if not has_axis(axis):
        return x
    return _alltoall(axis, x)


# ---------------------------------------------------------------------------
# fused collective-matmul pair (tuner arbitrates fused_ring vs unfused)
# ---------------------------------------------------------------------------


def _flat2(x):
    """Collapse leading dims: [..., K] -> ([T, K], T)."""
    t = math.prod(x.shape[:-1])
    return x.reshape(t, x.shape[-1]), t


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _agmm(axis: str, x, w):
    return api.allgather_matmul(x, w, axis)


def _agmm_fwd(axis, x, w):
    return _agmm(axis, x, w), (x, w)


def _agmm_bwd(axis, res, g):
    # out = all_gather(x) @ w.  dx reduces+scatters the per-shard partials
    # g @ w.T (the mirror fused op); dw re-gathers x (rematerialization —
    # the unfused composition would have kept the gathered copy alive).
    x, w = res
    with api.phase("bwd"):
        dx = api.matmul_reducescatter(g, w.T, axis)
        dw = jnp.matmul(api.allgather(x, axis).T, g)
    return dx, dw


_agmm.defvjp(_agmm_fwd, _agmm_bwd)


def allgather_matmul(x, w, axis: str = AXES.model):
    """``all_gather(x, rows) @ w`` — x per-shard ``[n, K]``, w shard-local
    ``[K, M]`` -> ``[p*n, M]``.  Fused-vs-unfused is a dispatcher decision;
    the backward pairs ``matmul_reducescatter`` for the input grad."""
    if not has_axis(axis):
        return jnp.matmul(x, w)
    return _agmm(axis, x, w)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mmrs(axis: str, x, w):
    return api.matmul_reducescatter(x, w, axis)


def _mmrs_fwd(axis, x, w):
    return _mmrs(axis, x, w), (x, w)


def _mmrs_bwd(axis, res, g):
    # out = reduce_scatter(x @ w).  The cotangent must be gathered anyway
    # (transpose of reduce-scatter); the fused op hands the assembled
    # all_gather(g) back so dw reuses it instead of gathering twice.
    x, w = res
    with api.phase("bwd"):
        dx, gg = api.allgather_matmul(g, w.T, axis, return_gathered=True)
        dw = jnp.matmul(x.T, gg)
    return dx, dw


_mmrs.defvjp(_mmrs_fwd, _mmrs_bwd)


def matmul_reducescatter(x, w, axis: str = AXES.model):
    """``reduce_scatter(x @ w, rows)`` — x per-shard ``[p*n, K]`` (partial
    contraction), w ``[K, M]`` -> ``[n, M]`` summed over ``axis``.  The
    backward pairs ``allgather_matmul`` (fused fwd <-> fused bwd)."""
    if not has_axis(axis):
        return jnp.matmul(x, w)
    return _mmrs(axis, x, w)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fsdp_mm(axis: str, x, w):
    x2, _ = _flat2(x)
    zt = api.allgather_matmul(jnp.swapaxes(w, 0, 1), x2.T, axis)
    return zt.T.reshape(*x.shape[:-1], zt.shape[0])


def _fsdp_mm_fwd(axis, x, w):
    # x @ AG(w, dim 1) == (AG(w.T, dim 0) @ x.T).T — the canonical
    # allgather-matmul with the WEIGHT as the gathered operand.  The ring
    # materializes the gathered weight anyway; keep it as the residual
    # (memory parity with the unfused fsdp_gather path, whose autodiff
    # saves the gathered weight too).
    x2, _ = _flat2(x)
    zt, wft = api.allgather_matmul(jnp.swapaxes(w, 0, 1), x2.T, axis,
                                   return_gathered=True)
    return zt.T.reshape(*x.shape[:-1], zt.shape[0]), (x, wft)


def _fsdp_mm_bwd(axis, res, g):
    # dw is the FSDP gradient reduce-scatter, fused with its matmul:
    # dw.T = reduce_scatter(g.T @ x, rows over data).  dx reuses the
    # gathered weight saved by the forward.
    x, wft = res
    g2, _ = _flat2(g)
    x2, _ = _flat2(x)
    with api.phase("bwd"):
        dwt = api.matmul_reducescatter(g2.T, x2, axis)
    dx = jnp.matmul(g2, wft).reshape(x.shape)
    return dx, jnp.swapaxes(dwt, 0, 1)


_fsdp_mm.defvjp(_fsdp_mm_fwd, _fsdp_mm_bwd)


def fsdp_matmul(x, w, axis: str = AXES.data):
    """``x @ all_gather(w, dim 1)`` with the ZeRO-3 weight gather fused into
    the matmul — the fsdp_gather→matmul sites of row-parallel weights.  The
    backward fuses the FSDP grad reduce-scatter the same way."""
    if not has_axis(axis):
        return jnp.matmul(x, w)
    return _fsdp_mm(axis, x, w)


# ---------------------------------------------------------------------------
# weight-stationary 2-D collective matmul (data-gather x model-reduce-scatter)
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _mm2d(rs_axis: str, ag_axis: str, x, w):
    return api.matmul_reducescatter_2d(x, w, rs_axis, ag_axis)


def _mm2d_fwd(rs_axis, ag_axis, x, w):
    # the outer ring materializes the col-gathered full weight anyway; keep
    # it as the residual so dx needs no re-gather of w (memory parity with
    # the unfused path, whose autodiff saves the gathered weight too)
    ys, wf = api.matmul_reducescatter_2d(x, w, rs_axis, ag_axis,
                                         return_gathered=True)
    return ys, (x, wf)


def _mm2d_bwd(rs_axis, ag_axis, res, g):
    # ys = RS_q(x @ AG_d(w)): the cotangent g arrives SHARDED over rs_axis.
    # dx = AG_q(g) @ Wᵀ — the 1-D gather-role fused op (transpose of the
    # reduce-scatter); dw is the fused 2-D TRANSPOSE schedule: the rs-axis
    # cotangent gather is CONTRACTED into the ag-axis reduce-scatter
    # (axes swap roles relative to the forward).
    x, wf = res
    with api.phase("bwd"):
        dx = api.allgather_matmul(g, jnp.swapaxes(wf, 0, 1), rs_axis)
        dwt = api.matmul_reducescatter_2d_t(g, x, ag_axis, rs_axis)
    return dx, jnp.swapaxes(dwt, 0, 1)


_mm2d.defvjp(_mm2d_fwd, _mm2d_bwd)


def matmul_reducescatter_2d(x, w, rs_axis: str = AXES.model,
                            ag_axis: str = AXES.data):
    """``reduce_scatter(x @ all_gather(w, cols over ag_axis), rows over
    rs_axis)`` — x ``[T, K]`` shard-local, w ``[K, M/d]`` the data-axis
    FSDP column block -> ``[T/q, M]`` summed over ``rs_axis``.  BOTH
    collectives fuse around one matmul (nested rings); fused-vs-unfused is
    a dispatcher decision per 2-D cell.  The backward pairs
    ``allgather_matmul`` for dx and the fused 2-D transpose schedule
    (``matmul_reducescatter_2d_t``) for dw.

    Degenerate axes fall back to the matching 1-D op.  Rows MUST divide
    the rs axis — the reduce-scatter contract has no well-defined output
    otherwise (same constraint as the 1-D ``matmul_reducescatter``);
    callers like ``row_matmul(fsdp_dim=1)`` guard this and keep the 1-D
    ``tp_allreduce(fsdp_matmul(...))`` composition for ragged rows.
    """
    if not has_axis(ag_axis):
        return matmul_reducescatter(x, w, rs_axis)
    if not has_axis(rs_axis):
        return fsdp_matmul(x, w, ag_axis)
    if x.shape[0] % axis_size(rs_axis) != 0:
        raise ValueError(
            f"matmul_reducescatter_2d: rows {x.shape[0]} must divide the "
            f"rs axis size {axis_size(rs_axis)}; use the unfused "
            "tp_allreduce(fsdp_matmul(...)) composition for ragged rows "
            "(row_matmul(fsdp_dim=1) does this automatically)")
    return _mm2d(rs_axis, ag_axis, x, w)


# ---------------------------------------------------------------------------
# Megatron matmuls
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _acc_mm(axis: str, x, w):
    x2, _ = _flat2(x)
    out = api.matmul_accumulate(x2, w, axis)
    return out.reshape(*x.shape[:-1], w.shape[-1])


def _acc_mm_fwd(axis, x, w):
    # x @ AG(w, dim 0): the gathered dim is contracted away — the accumulate
    # ring.  The ring materializes the full weight anyway; keep it as the
    # residual so dx is a local matmul (memory parity with the unfused
    # fsdp_gather path, whose autodiff saves the gathered weight too).
    x2, _ = _flat2(x)
    out, wf = api.matmul_accumulate(x2, w, axis, return_gathered=True)
    return out.reshape(*x.shape[:-1], w.shape[-1]), (x, wf)


def _acc_mm_bwd(axis, res, g):
    # out = x @ W with W = AG(w, rows).  dw is W's cotangent (x.T @ g)
    # reduce-scattered back to the K-row owner shards — the mirror fused op;
    # dx reuses the gathered weight saved by the forward.
    x, wf = res
    g2, _ = _flat2(g)
    x2, _ = _flat2(x)
    with api.phase("bwd"):
        dw = api.matmul_reducescatter(x2.T, g2, axis)
    dx = jnp.matmul(g2, wf.T).reshape(x.shape)
    return dx, dw


_acc_mm.defvjp(_acc_mm_fwd, _acc_mm_bwd)


def matmul_accumulate(x, w, axis: str = AXES.data):
    """``x @ all_gather(w, dim 0)`` with the K-dim (contraction) weight
    gather fused into the matmul — the ``fsdp_gather(w, 0)`` + matmul
    sites.  ``w`` per-shard ``[K/p, M]``, ``x`` ``[..., K]``.  The gathered
    dim is contracted away, so the row-block rings don't apply; the
    dispatcher arbitrates the accumulate ring vs the unfused composition
    per cell.  The backward pairs ``matmul_reducescatter`` for the weight
    grad (the FSDP reduce-scatter over K rows).

    Unevenly padded shards (``x``'s K != p·rows(w)) fall back to the tuned
    unfused gather + slice — the ring needs equal blocks.
    """
    if not has_axis(axis):
        return jnp.matmul(x, w)
    k = x.shape[-1]
    if k != axis_size(axis) * w.shape[0]:
        full = _gather(0, axis, w)[:k]
        return jnp.matmul(x, full)
    return _acc_mm(axis, x, w)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _col_mm(axis: str, x, w):
    return jnp.matmul(x, w)


def _col_mm_fwd(axis, x, w):
    return jnp.matmul(x, w), (x, w)


def _col_mm_bwd(axis, res, g):
    # dx = allreduce(g @ w.T) decomposed as reduce-scatter + all-gather so
    # the matmul half is fused-selectable; single all-reduce when the row
    # count does not divide the axis.
    x, w = res
    g2, t = _flat2(g)
    x2, _ = _flat2(x)
    with api.phase("bwd"):
        if t % axis_size(axis) == 0:
            ds = api.matmul_reducescatter(g2, w.T, axis)
            dx = api.allgather(ds, axis).reshape(x.shape)
        else:
            dx = api.allreduce(jnp.matmul(g2, w.T), axis).reshape(x.shape)
    dw = jnp.matmul(x2.T, g2)
    return dx, dw


_col_mm.defvjp(_col_mm_fwd, _col_mm_bwd)


def col_matmul(x, w, axis: str = AXES.model, *, fsdp_dim: int | None = None,
               fsdp_axis: str = AXES.data):
    """Column-parallel matmul: ``x`` replicated, ``w`` sharded on its output
    dim -> output sharded on the last dim.  No forward collective; the input
    grad is summed over the axis — via the fused-selectable
    ``matmul_reducescatter`` + all-gather decomposition.

    ``fsdp_dim=0`` declares that ``w`` is additionally FSDP-sharded on its
    INPUT (contraction) dim over ``fsdp_axis`` and fuses that gather into
    the matmul via ``matmul_accumulate`` — the K-dim weight-gather sites;
    the model-axis input-grad sum is carried by a ``tp_copy`` marker.
    Other ``fsdp_dim`` values gather unfused first."""
    if fsdp_dim == 0:
        return matmul_accumulate(tp_copy(x, axis), w, fsdp_axis)
    if fsdp_dim is not None:
        w = fsdp_gather(w, fsdp_dim, fsdp_axis)
    if not has_axis(axis):
        return jnp.matmul(x, w)
    return _col_mm(axis, x, w)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _row_mm(axis: str, x, w):
    x2, _ = _flat2(x)
    ys = api.matmul_reducescatter(x2, w, axis)
    return api.allgather(ys, axis).reshape(*x.shape[:-1], w.shape[-1])


def _row_mm_fwd(axis, x, w):
    return _row_mm(axis, x, w), (x, w)


def _row_mm_bwd(axis, res, g):
    # the reduced output is ONE logical replicated tensor (Megatron "g");
    # its replicated cotangent needs no collective — identical to the
    # monolithic all-reduce formulation's identity backward
    x, w = res
    g2, _ = _flat2(g)
    x2, _ = _flat2(x)
    dx = jnp.matmul(g2, w.T).reshape(x.shape)
    dw = jnp.matmul(x2.T, g2)
    return dx, dw


_row_mm.defvjp(_row_mm_fwd, _row_mm_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _row2d_mm(rs_axis: str, ag_axis: str, x, w):
    x2, _ = _flat2(x)
    ys = api.matmul_reducescatter_2d(x2, w, rs_axis, ag_axis)
    return api.allgather(ys, rs_axis).reshape(*x.shape[:-1], ys.shape[-1])


def _row2d_fwd(rs_axis, ag_axis, x, w):
    x2, _ = _flat2(x)
    ys, wf = api.matmul_reducescatter_2d(x2, w, rs_axis, ag_axis,
                                         return_gathered=True)
    y = api.allgather(ys, rs_axis).reshape(*x.shape[:-1], ys.shape[-1])
    return y, (x, wf)


def _row2d_bwd(rs_axis, ag_axis, res, g):
    # the reduced output is ONE logical replicated tensor (Megatron "g"):
    # its replicated cotangent needs no collective for dx (local matmul
    # against the saved col-gathered weight).  dw re-enters the rs-axis
    # row shard of g and runs the fused 2-D TRANSPOSE schedule — the
    # rs-axis re-gather is contracted into the ag-axis FSDP grad
    # reduce-scatter, both tuner-arbitrated.
    x, wf = res
    g2, t = _flat2(g)
    x2, _ = _flat2(x)
    t_loc = t // axis_size(rs_axis)
    gs = jax.lax.dynamic_slice_in_dim(g2, axis_index(rs_axis) * t_loc,
                                      t_loc, axis=0)
    with api.phase("bwd"):
        dwt = api.matmul_reducescatter_2d_t(gs, x2, ag_axis, rs_axis)
    dx = jnp.matmul(g2, jnp.swapaxes(wf, 0, 1)).reshape(x.shape)
    return dx, jnp.swapaxes(dwt, 0, 1)


_row2d_mm.defvjp(_row2d_fwd, _row2d_bwd)


def row_matmul(x, w, axis: str = AXES.model, *, fsdp_dim: int | None = None,
               fsdp_axis: str = AXES.data):
    """Row-parallel matmul: ``x`` sharded on the last dim, ``w`` sharded on
    its input dim -> partial products summed over the model axis.  The sum
    is issued as reduce-scatter + all-gather so the matmul half is the
    fused-selectable ``matmul_reducescatter`` (single tuned all-reduce when
    the row count does not divide the axis).  The backward needs no
    collective (cotangent is replicated).

    ``fsdp_dim=1`` declares that ``w`` is additionally FSDP-sharded on its
    OUTPUT dim over ``fsdp_axis`` and fuses BOTH collectives around the
    matmul via the weight-stationary 2-D op (``matmul_reducescatter_2d``:
    outer data-axis weight stream, inner model-axis reduce-scatter; the
    replicating model-axis all-gather of the scattered rows stays a
    classic tuned collective).  When either axis is missing — or the row
    count does not divide the model axis — it falls back to the 1-D
    composition ``tp_allreduce(fsdp_matmul(...))``; other ``fsdp_dim``
    values gather unfused first."""
    if fsdp_dim == 1:
        if (has_axis(axis) and has_axis(fsdp_axis)
                and math.prod(x.shape[:-1]) % axis_size(axis) == 0):
            return _row2d_mm(axis, fsdp_axis, x, w)
        return tp_allreduce(fsdp_matmul(x, w, fsdp_axis), axis)
    if fsdp_dim is not None:
        w = fsdp_gather(w, fsdp_dim, fsdp_axis)
    if not has_axis(axis):
        return jnp.matmul(x, w)
    if math.prod(x.shape[:-1]) % axis_size(axis) == 0:
        return _row_mm(axis, x, w)
    return tp_allreduce(jnp.matmul(x, w), axis)
