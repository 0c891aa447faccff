"""dist/ops must dispatch through ``repro.core.api`` — never hard-wire
``jax.lax`` — so ``api.tuned(force=...)`` and ``PGTUNE_MODULE`` redirect
model-parallel traffic to guideline mock-ups, forward AND backward.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import api
from repro.dist import ops

P = 4


def _w():
    return jnp.arange(P * 4 * 2, dtype=jnp.float32).reshape(P, 4, 2)


def _gather_loss(ws):
    full = ops.fsdp_gather(ws, 0, "data")
    return jnp.sum(full * full)


def _impls(record, op):
    return {impl for o, _, _, impl, _ph in record if o == op}


# ---------------------------------------------------------------------------
# force= context table
# ---------------------------------------------------------------------------


def test_force_reaches_fsdp_gather_fwd_and_bwd():
    w = _w()
    with api.tuned(force={"allgather": "allgather_as_allreduce",
                          "reducescatter": "rsb_as_allreduce"}) as ctx:
        g = jax.vmap(jax.grad(_gather_loss), axis_name="data")(w)
    # forward allgather AND backward reducescatter both went through the
    # context with the forced selections
    assert _impls(ctx.record, "allgather") == {"allgather_as_allreduce"}
    assert _impls(ctx.record, "reducescatter") == {"rsb_as_allreduce"}
    np.testing.assert_allclose(np.asarray(g), np.asarray(2 * w * P),
                               rtol=1e-6)


def test_swapping_forced_impl_changes_selection_not_values():
    w = _w()
    results = {}
    for impl in ("default", "allgather_as_ring", "allgather_as_alltoall"):
        with api.tuned(force={"allgather": impl}) as ctx:
            results[impl] = jax.vmap(jax.grad(_gather_loss),
                                     axis_name="data")(w)
        assert _impls(ctx.record, "allgather") == {impl}, impl
    base = np.asarray(results["default"])
    for impl, got in results.items():
        np.testing.assert_allclose(np.asarray(got), base, rtol=1e-6,
                                   err_msg=impl)


def test_force_reaches_every_dist_op():
    x = jnp.arange(P * P * 2 * 3, dtype=jnp.float32).reshape(P, P * 2, 3)
    force = {"allreduce": "allreduce_as_reduce_bcast",
             "alltoall": "alltoall_as_ppermute",
             "allgather": "allgather_as_allreduce",
             "reducescatter": "rsb_as_reduce_scatter"}

    def f(a):
        y = ops.tp_allreduce(a, "model")
        y = ops.tp_copy(y, "model") * 0.5
        y = ops.ep_alltoall(y, "model")
        y = ops.fsdp_gather(ops.tp_reducescatter(y, 0, "model"), 0, "model")
        return jnp.sum(y * a)

    with api.tuned(force=force) as ctx:
        jax.vmap(jax.grad(f), axis_name="model")(x)
    for op, impl in force.items():
        assert impl in _impls(ctx.record, op), (op, ctx.record)


# ---------------------------------------------------------------------------
# PGTUNE_MODULE env routing (the paper's CLI --module= syntax)
# ---------------------------------------------------------------------------


def test_env_module_spec_reaches_fsdp_gather(monkeypatch):
    monkeypatch.setenv("PGTUNE_MODULE",
                       "allgather:alg=allgather_as_gather_bcast")
    w = _w()
    with api.tuned() as ctx:
        y = jax.vmap(lambda a: ops.fsdp_gather(a, 0, "data"),
                     axis_name="data")(w)
    assert _impls(ctx.record, "allgather") == {"allgather_as_gather_bcast"}
    np.testing.assert_allclose(
        np.asarray(y), np.broadcast_to(np.asarray(w).reshape(P * 4, 2),
                                       (P, P * 4, 2)), rtol=1e-6)


def test_context_force_beats_env(monkeypatch):
    monkeypatch.setenv("PGTUNE_MODULE", "allgather:alg=allgather_as_alltoall")
    with api.tuned(force={"allgather": "allgather_as_ring"}) as ctx:
        jax.vmap(lambda a: ops.fsdp_gather(a, 0, "data"),
                 axis_name="data")(_w())
    assert _impls(ctx.record, "allgather") == {"allgather_as_ring"}


# ---------------------------------------------------------------------------
# end-to-end: models.lm forward+backward is intercepted (acceptance check)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ag_impl", ["allgather_as_allreduce",
                                     "allgather_as_ring"])
def test_lm_fwd_bwd_dispatches_both_directions(ag_impl):
    from repro.configs import get_config
    from repro.models import lm
    from repro.models.params import init_tree

    cfg = get_config("llama3.2-3b").smoke()
    D = 2  # FSDP degree (vmap axis emulation)
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32) + 5}
    batch["labels"] = batch["tokens"]

    def init(key):
        return init_tree(lm.model_specs(cfg, tp=1), key)

    def grad_fn(params):
        return jax.grad(lambda p: lm.loss_fn(p, cfg, batch)[0])(params)

    with api.tuned(force={"allgather": ag_impl,
                          "reducescatter": "rsb_as_allreduce"}) as ctx:
        params = jax.vmap(init, axis_name="data", axis_size=D,
                          in_axes=None, out_axes=0)(jax.random.key(0))
        g = jax.vmap(grad_fn, axis_name="data")(params)

    # forward direction: every FSDP param gather took the forced mock-up
    assert _impls(ctx.record, "allgather") == {ag_impl}
    # backward direction: grads reduce-scattered through the forced mock-up
    assert _impls(ctx.record, "reducescatter") == {"rsb_as_allreduce"}
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))


def test_lm_swapped_force_changes_recorded_selection():
    """Same model trace, different force table -> different selections in
    ``TuneContext.record`` — proving dist ops are intercepted, not
    hard-wired to jax.lax."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.models.params import init_tree

    cfg = get_config("llama3.2-3b").smoke()
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32) + 5}
    batch["labels"] = batch["tokens"]

    def run(force):
        def init(key):
            return init_tree(lm.model_specs(cfg, tp=1), key)

        def grad_fn(params):
            return jax.grad(lambda p: lm.loss_fn(p, cfg, batch)[0])(params)

        with api.tuned(force=force) as ctx:
            params = jax.vmap(init, axis_name="data", axis_size=2,
                              in_axes=None, out_axes=0)(jax.random.key(0))
            jax.vmap(grad_fn, axis_name="data")(params)
        return ctx

    a = run({"allgather": "allgather_as_allreduce"})
    b = run({"allgather": "default"})
    assert _impls(a.record, "allgather") == {"allgather_as_allreduce"}
    assert _impls(b.record, "allgather") == {"default"}
    # both directions present in both runs
    for ctx in (a, b):
        assert _impls(ctx.record, "reducescatter"), "no backward collectives"
