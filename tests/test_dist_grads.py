"""Gradient properties for every ``dist.ops`` primitive under
``vmap(axis_name=...)`` emulation: ``jax.grad`` of the api-routed op must
match a pure-``lax.psum``/``all_gather`` reference implementing the same
fwd/bwd pairing — to rtol 1e-6, with defaults AND with guideline mock-ups
forced, so the tuner can swap algorithms without perturbing training.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import api
from repro.core._axis import tie_to_axis
from repro.dist import ops

P = 4
AXIS = "model"


# ---------------------------------------------------------------------------
# pure-lax references with the same custom-VJP pairing
# ---------------------------------------------------------------------------


def _moved(fn, x, dim):
    if dim in (0, -x.ndim):
        return fn(x)
    return jnp.moveaxis(fn(jnp.moveaxis(x, dim, 0)), 0, dim)


def _lax_ag(x, axis):
    return lax.all_gather(x, axis, axis=0, tiled=True)


def _lax_rs(x, axis):
    return lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def ref_gather(dim, axis, x):
    return _moved(lambda a: _lax_ag(a, axis), x, dim)


ref_gather.defvjp(
    lambda dim, axis, x: (ref_gather(dim, axis, x), None),
    lambda dim, axis, _, g: (_moved(lambda a: _lax_rs(a, axis), g, dim),))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def ref_scatter(dim, axis, x):
    return _moved(lambda a: _lax_rs(a, axis), x, dim)


ref_scatter.defvjp(
    lambda dim, axis, x: (ref_scatter(dim, axis, x), None),
    lambda dim, axis, _, g: (_moved(lambda a: _lax_ag(a, axis), g, dim),))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def ref_gather_split(dim, axis, x):
    return _moved(lambda a: _lax_ag(a, axis), x, dim)


def _own_block(dim, axis, g):
    n = g.shape[dim] // lax.axis_size(axis)
    return lax.dynamic_slice_in_dim(g, lax.axis_index(axis) * n, n, dim)


ref_gather_split.defvjp(
    lambda dim, axis, x: (ref_gather_split(dim, axis, x), None),
    lambda dim, axis, _, g: (_own_block(dim, axis, g),))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def ref_allreduce(axis, x):
    return lax.psum(x, axis)


ref_allreduce.defvjp(lambda axis, x: (ref_allreduce(axis, x), None),
                     lambda axis, _, g: (g,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def ref_psum_grad(axis, x):
    return x


ref_psum_grad.defvjp(lambda axis, x: (x, None),
                     lambda axis, _, g: (lax.psum(g, axis),))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def ref_alltoall(axis, x):
    return lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)


ref_alltoall.defvjp(
    lambda axis, x: (ref_alltoall(axis, x), None),
    lambda axis, _, g: (ref_alltoall(axis, tie_to_axis(g, axis)),))


# ---------------------------------------------------------------------------
# harness: grad of <y, c(y)> with a fixed deterministic cotangent
# ---------------------------------------------------------------------------


def _cotangent(y):
    return jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(y.shape)


def _grad_of(f, x):
    def loss(a):
        y = f(a)
        return jnp.sum(y * _cotangent(y))
    return np.asarray(jax.vmap(jax.grad(loss), axis_name=AXIS)(x))


def _x(rows=P * 2, width=6):
    k = jax.random.key(0)
    return jax.random.normal(k, (P, rows, width), jnp.float32)


MOCKUP_FORCE = {"allgather": "allgather_as_allreduce",
                "reducescatter": "rsb_as_allreduce",
                "allreduce": "allreduce_as_reduce_bcast",
                "alltoall": "alltoall_as_ppermute"}

FORCES = [pytest.param(None, id="defaults"),
          pytest.param(MOCKUP_FORCE, id="mockups")]

CASES = [
    ("fsdp_gather_d0", lambda a: ops.fsdp_gather(a, 0, AXIS),
     lambda a: ref_gather(0, AXIS, a)),
    ("fsdp_gather_d1", lambda a: ops.fsdp_gather(a, 1, AXIS),
     lambda a: ref_gather(1, AXIS, a)),
    ("fsdp_gather_last", lambda a: ops.fsdp_gather(a, a.ndim - 1, AXIS),
     lambda a: ref_gather(1, AXIS, a)),
    ("tp_allgather_split_last",
     lambda a: ops.tp_allgather_split(a, a.ndim - 1, AXIS),
     lambda a: ref_gather_split(1, AXIS, a)),
    ("tp_reducescatter", lambda a: ops.tp_reducescatter(a, 0, AXIS),
     lambda a: ref_scatter(0, AXIS, a)),
    ("tp_allreduce", lambda a: ops.tp_allreduce(a, AXIS),
     lambda a: ref_allreduce(AXIS, a)),
    ("tp_copy", lambda a: ops.tp_copy(a, AXIS),
     lambda a: ref_psum_grad(AXIS, a)),
    ("tp_psum_grad", lambda a: ops.tp_psum_grad(a, AXIS),
     lambda a: ref_psum_grad(AXIS, a)),
    ("ep_alltoall", lambda a: ops.ep_alltoall(a, AXIS),
     lambda a: ref_alltoall(AXIS, a)),
]


@pytest.mark.parametrize("force", FORCES)
@pytest.mark.parametrize("name,f_ops,f_ref", CASES,
                         ids=[c[0] for c in CASES])
def test_grad_matches_pure_lax_reference(name, f_ops, f_ref, force):
    x = _x()
    want = _grad_of(f_ref, x)
    with api.tuned(force=force or {}) as ctx:
        got = _grad_of(f_ops, x)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert ctx.record, "op did not dispatch through the api"


@pytest.mark.parametrize("force", FORCES)
def test_matmul_grads_match_reference(force):
    x = _x(rows=5, width=8)                       # replicated activation
    w = jax.random.normal(jax.random.key(1), (P, 8, 3))   # col-sharded
    wr = jax.random.normal(jax.random.key(2), (P, 3, 8))  # row-sharded

    def f_ops(a, wc, wrr):
        h = ops.col_matmul(a, wc, AXIS)
        return ops.row_matmul(h, wrr, AXIS)

    def f_ref(a, wc, wrr):
        h = jnp.matmul(ref_psum_grad(AXIS, a), wc)
        return ref_allreduce(AXIS, jnp.matmul(h, wrr))

    def grads(f):
        def loss(a, wc, wrr):
            y = f(a, wc, wrr)
            return jnp.sum(y * _cotangent(y))
        return jax.vmap(jax.grad(loss, argnums=(0, 1, 2)),
                        axis_name=AXIS)(x, w, wr)

    want = grads(f_ref)
    with api.tuned(force=force or {}):
        got = grads(f_ops)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-6, atol=1e-6)


def test_second_order_through_tp_allreduce():
    """grad-of-grad still routes through the dispatcher (hessian-vector
    products during e.g. sharpness probes must stay tuned)."""
    x = jnp.ones((P, 3), jnp.float32)

    def f(a):
        return jnp.sum(ops.tp_allreduce(a * a, AXIS))

    with api.tuned() as ctx:
        g = jax.vmap(jax.grad(lambda a: jnp.sum(jax.grad(f)(a) * a)),
                     axis_name=AXIS)(x)
    assert bool(jnp.all(jnp.isfinite(g)))
    assert any(op == "allreduce" for op, *_ in ctx.record)
