"""Fused collective-matmul: kernels, dispatcher ops, VJPs, tuner, fast path.

Interpret-mode / vmap equivalence of ``allgather_matmul`` and
``matmul_reducescatter`` (fused_ring vs the unfused composition) in fwd and
bwd across shapes, dtypes and non-divisible row counts; tuner selection of
fused-vs-unfused per shape (the new guideline); the measured-backend trace
replay skip rule; and the dispatch hot-path short-circuit.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import api, costmodel as cm, tuner
from repro.core import collectives as C
from repro.core.trace import Trace, TraceEntry
from repro.dist import ops
from repro.kernels.collective_matmul import (pallas_matmul,
                                             ring_allgather_matmul,
                                             ring_matmul_reducescatter)

PS = (4, 8)


def _cot(y):
    return jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(y.shape)


# ---------------------------------------------------------------------------
# tier-2 Pallas block matmul (interpret mode on CPU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-4),
                                        (jnp.bfloat16, 5e-1)])
@pytest.mark.parametrize("m,k,n", [
    (128, 128, 128),       # aligned
    (192, 64, 96),         # multi-block
    (100, 33, 17),         # nothing divides the tile
    (5, 256, 128),         # skinny rows
])
def test_pallas_matmul_interpret(rng, dtype, atol, m, k, n):
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    w = jnp.asarray(rng.normal(size=(k, n)), dtype)
    got = pallas_matmul(x, w, bm=64, bn=64, bk=64, interpret=True)
    want = jnp.matmul(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


# ---------------------------------------------------------------------------
# fused rings vs unfused composition (vmap semantic path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", PS)
@pytest.mark.parametrize("dtype,atol", [(np.float32, 1e-4),
                                        (np.float16, 2e-2)])
@pytest.mark.parametrize("n,k,m", [(4, 8, 6), (5, 3, 7), (1, 16, 2)])
def test_ring_allgather_matmul_matches_unfused(rng, p, dtype, atol, n, k, m):
    x = jnp.asarray(rng.normal(size=(p, n, k)).astype(dtype))
    w = jnp.asarray(rng.normal(size=(k, m)).astype(dtype))
    got = jax.vmap(lambda a: ring_allgather_matmul(a, w, "x"),
                   axis_name="x")(x)
    full = np.asarray(x, np.float32).reshape(p * n, k)
    want = full @ np.asarray(w, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32)[0], want,
                               atol=atol)
    # every shard holds the same full product
    for r in range(p):
        np.testing.assert_array_equal(np.asarray(got)[r], np.asarray(got)[0])


@pytest.mark.parametrize("p", PS)
@pytest.mark.parametrize("n,k,m", [(4, 8, 6), (3, 5, 2)])
def test_ring_matmul_reducescatter_matches_unfused(rng, p, n, k, m):
    x = jnp.asarray(rng.normal(size=(p, p * n, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(k, m)).astype(np.float32))
    got = jax.vmap(lambda a: ring_matmul_reducescatter(a, w, "x"),
                   axis_name="x")(x)
    want = (np.asarray(x) @ np.asarray(w)).sum(0).reshape(p, n, m)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)


def test_ring_allgather_matmul_returns_gathered(rng):
    p, n, k = 4, 3, 6
    x = jnp.asarray(rng.normal(size=(p, n, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(k, 5)).astype(np.float32))
    _, gath = jax.vmap(
        lambda a: ring_allgather_matmul(a, w, "x", return_gathered=True),
        axis_name="x")(x)
    np.testing.assert_allclose(np.asarray(gath)[0],
                               np.asarray(x).reshape(p * n, k), atol=1e-6)


@pytest.mark.parametrize("op", ["allgather_matmul", "matmul_reducescatter"])
@pytest.mark.parametrize("impl_check", [True])
def test_registry_impls_semantics(rng, op, impl_check):
    """Every registered impl of the fused ops against the dense oracle."""
    p, n, k, m = 4, 3, 6, 5
    rows = n if op == "allgather_matmul" else p * n
    x = rng.normal(size=(p, rows, k)).astype(np.float32)
    w = jnp.asarray(rng.normal(size=(k, m)).astype(np.float32))
    if op == "allgather_matmul":
        want = np.asarray(x).reshape(p * n, k) @ np.asarray(w)
        want = np.broadcast_to(want, (p,) + want.shape)
    else:
        want = (x @ np.asarray(w)).sum(0).reshape(p, n, m)
    from repro.core.selfcheck import rel_err, wire_hops
    from repro.kernels.quant import wire_tol
    for name in C.impl_names(op):
        impl = C.REGISTRY[op][name]
        got = jax.vmap(lambda a, fn=impl.fn: fn(a, "x", w=w),
                       axis_name="x")(jnp.asarray(x))
        if impl.wire_dtype is not None:
            # quantized-wire impls are approximate by design: gate at
            # their selfcheck tolerance instead of the exact atol
            tol = wire_tol(impl.wire_dtype, wire_hops(op, p))
            assert rel_err(got, want) <= tol, (name, rel_err(got, want))
        else:
            np.testing.assert_allclose(np.asarray(got), want, atol=1e-4,
                                       err_msg=name)


# ---------------------------------------------------------------------------
# dist.ops custom VJPs: fused grads == unfused grads == dense reference
# ---------------------------------------------------------------------------


def _grads(f, *args):
    def loss(*a):
        y = f(*a)
        return jnp.sum(y * _cot(y))
    return jax.vmap(jax.grad(loss, argnums=tuple(range(len(args)))),
                    axis_name="model")(*args)


@pytest.mark.parametrize("impl", ["default", "fused_ring"])
def test_allgather_matmul_grads(rng, impl):
    p, n, k, m = 4, 3, 8, 5
    x = jnp.asarray(rng.normal(size=(p, n, k)).astype(np.float32))
    w = jnp.asarray(np.broadcast_to(
        rng.normal(size=(k, m)).astype(np.float32), (p, k, m)).copy())

    def f(a, ww):
        return ops.allgather_matmul(a, ww, "model")

    with api.tuned(force={"allgather_matmul": impl,
                          "matmul_reducescatter": impl}) as ctx:
        dx, dw = _grads(f, x, w)
    # reference: unfused composition with the same gather<->scatter pairing
    def ref(a, ww):
        full = ops.fsdp_gather(a, 0, "model")
        return jnp.matmul(full, ww)

    rx, rw = _grads(ref, x, w)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(rx), atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(rw), atol=1e-5)
    # backward pairing: the input grad went through matmul_reducescatter
    assert any(op == "matmul_reducescatter" and ph == "bwd"
               for op, _, _, _, ph in ctx.record)


@pytest.mark.parametrize("impl", ["default", "fused_ring"])
def test_matmul_reducescatter_grads(rng, impl):
    p, n, k, m = 4, 2, 6, 5
    x = jnp.asarray(rng.normal(size=(p, p * n, k)).astype(np.float32))
    w = jnp.asarray(np.broadcast_to(
        rng.normal(size=(k, m)).astype(np.float32), (p, k, m)).copy())

    def f(a, ww):
        return ops.matmul_reducescatter(a, ww, "model")

    with api.tuned(force={"allgather_matmul": impl,
                          "matmul_reducescatter": impl}) as ctx:
        dx, dw = _grads(f, x, w)

    def ref(a, ww):
        return ops.tp_reducescatter(jnp.matmul(a, ww), 0, "model")

    rx, rw = _grads(ref, x, w)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(rx), atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(rw), atol=1e-5)
    # fused fwd pairs with allgather_matmul bwd
    assert any(op == "allgather_matmul" and ph == "bwd"
               for op, _, _, _, ph in ctx.record)


@pytest.mark.parametrize("impl", ["default", "fused_ring"])
def test_fsdp_matmul_fuses_weight_gather(rng, impl):
    """x @ AG(w, dim 1) over the data axis — values and grads must match
    the unfused fsdp_gather + matmul composition exactly."""
    p, b, s, f, dloc = 4, 2, 3, 6, 2
    x = jnp.asarray(rng.normal(size=(p, b, s, f)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(p, f, dloc)).astype(np.float32))

    def g_of(fun):
        def loss(a, ww):
            y = fun(a, ww)
            return jnp.sum(y * _cot(y))
        return jax.vmap(jax.grad(loss, argnums=(0, 1)),
                        axis_name="data")(x, w)

    def fused(a, ww):
        return ops.fsdp_matmul(a, ww, "data")

    def unfused(a, ww):
        return jnp.matmul(a, ops.fsdp_gather(ww, 1, "data"))

    with api.tuned(force={"allgather_matmul": impl,
                          "matmul_reducescatter": impl}) as ctx:
        got_y = jax.vmap(fused, axis_name="data")(x, w)
        gx, gw = g_of(fused)
    ref_y = jax.vmap(unfused, axis_name="data")(x, w)
    rx, rw = g_of(unfused)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(ref_y),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), atol=1e-5)
    # fwd weight gather fused; bwd grad reduce-scatter fused
    assert any(op == "allgather_matmul" and ph == "fwd"
               for op, _, _, _, ph in ctx.record)
    assert any(op == "matmul_reducescatter" and ph == "bwd"
               for op, _, _, _, ph in ctx.record)


@pytest.mark.parametrize("rows", [8, 5])     # divisible and not
@pytest.mark.parametrize("impl", ["default", "fused_ring"])
def test_col_row_matmul_rewired_grads_match_legacy(rng, rows, impl):
    """col/row matmul through the fused-selectable decomposition must equal
    the legacy psum formulation in values AND grads (any impl)."""
    p = 4
    x = jnp.asarray(rng.normal(size=(p, rows, 6)).astype(np.float32))
    wc = jnp.asarray(rng.normal(size=(p, 6, 3)).astype(np.float32))
    wr = jnp.asarray(rng.normal(size=(p, 3, 6)).astype(np.float32))

    def f(a, c, r):
        h = ops.col_matmul(a, c, "model")
        return ops.row_matmul(h, r, "model")

    def ref(a, c, r):
        h = jnp.matmul(ops.tp_copy(a, "model"), c)
        return ops.tp_allreduce(jnp.matmul(h, r), "model")

    def grads(fun):
        def loss(a, c, r):
            y = fun(a, c, r)
            return jnp.sum(y * _cot(y))
        return jax.vmap(jax.grad(loss, argnums=(0, 1, 2)),
                        axis_name="model")(x, wc, wr)

    want_y = jax.vmap(ref, axis_name="model")(x, wc, wr)
    want_g = grads(ref)
    with api.tuned(force={"allgather_matmul": impl,
                          "matmul_reducescatter": impl}):
        got_y = jax.vmap(f, axis_name="model")(x, wc, wr)
        got_g = grads(f)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=1e-5)
    for g, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-5)


def test_row_matmul_fsdp_dim1_matches_pregathered(rng):
    p = 4
    x = jnp.asarray(rng.normal(size=(p, 8, 6)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(p, 6, 2)).astype(np.float32))
    # model axis absent, data axis bound: fsdp_dim=1 fuses the data gather
    got = jax.vmap(lambda a, ww: ops.row_matmul(a, ww, fsdp_dim=1),
                   axis_name="data")(x, w)
    ref = jax.vmap(lambda a, ww: jnp.matmul(a, ops.fsdp_gather(ww, 1)),
                   axis_name="data")(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# tuner: the fused-vs-unfused guideline per shape
# ---------------------------------------------------------------------------


def test_tuner_selects_fused_large_default_small():
    rep = tuner.tune(ops=["allgather_matmul", "matmul_reducescatter"],
                     sizes=(64, 1024, 1_048_576, 16_777_216),
                     axis_size=8, backend=tuner.CostModelBackend(cm.V5E_ICI))
    prof = rep.profiles
    for op in ("allgather_matmul", "matmul_reducescatter"):
        assert prof.lookup(op, 8, 16_777_216) == "fused_ring", op
        assert prof.lookup(op, 8, 64) is None, op      # default kept


def test_tune_trace_phase_profiles_pick_fused_for_tp_shapes():
    """A trace with a realistic TP matmul cell and a tiny one: the phase
    store must route the big cell to fused_ring and keep the small cell on
    the default — the acceptance-criterion shape split.  (Geometry-less
    cells: the canonical cost-model pricing still applies.)"""
    t = Trace([TraceEntry.of("allgather_matmul", 8, 4_194_304, "decode",
                             "default", 10),
               TraceEntry.of("allgather_matmul", 8, 256, "decode",
                             "default", 10),
               TraceEntry.of("matmul_reducescatter", 8, 8_388_608, "bwd",
                             "default", 4)])
    rep = tuner.tune_trace(t, backend=tuner.CostModelBackend(cm.V5E_ICI))
    dec = rep.phase_profiles["decode"]
    assert dec.lookup("allgather_matmul", 8, 4_194_304) == "fused_ring"
    assert dec.lookup("allgather_matmul", 8, 256) is None
    bwd = rep.phase_profiles["bwd"]
    assert bwd.lookup("matmul_reducescatter", 8, 8_388_608) == "fused_ring"
    assert rep.est_tuned_s["decode"] < rep.est_default_s["decode"]


def test_lm_train_trace_contains_fused_ops_and_tuner_splits(rng):
    """End-to-end: a recorded fwd+bwd LM step (vmap FSDP) now emits
    allgather_matmul (fused weight gather) and matmul_reducescatter (grad
    reduce-scatter) cells, and trace-replay tuning on the cost model picks
    fused_ring for at least one of them."""

    from repro.configs import get_config
    from repro.models import lm
    from repro.models.params import init_tree

    cfg = get_config("llama3.2-3b").smoke()
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32) + 5}
    batch["labels"] = batch["tokens"]

    def init(key):
        return init_tree(lm.model_specs(cfg, tp=1), key)

    def grad_fn(params):
        return jax.grad(lambda p: lm.loss_fn(p, cfg, batch)[0])(params)

    with api.tuned() as ctx:
        params = jax.vmap(init, axis_name="data", axis_size=2,
                          in_axes=None, out_axes=0)(jax.random.key(0))
        jax.vmap(grad_fn, axis_name="data")(params)

    trace = Trace.from_context(ctx)
    assert any(c.op == "allgather_matmul" for c in trace.cells("fwd"))
    assert any(c.op == "matmul_reducescatter"
               for c in trace.cells("bwd"))
    # every fused cell must carry its callsite's true GEMM geometry
    for c in trace.cells():
        if c.op in ("allgather_matmul", "matmul_reducescatter",
                    "matmul_accumulate"):
            assert c.fused and c.mm_role, c
    # smoke-config payloads are tiny (fusion correctly loses there); replay
    # the same op mix with every recorded GEMM grown to production dims
    # (the paper's "profiles are per cell" point, now with true geometry:
    # the overlap is priced from the cell's actual flops) — and the tuner
    # must flip the fused collective-matmul cells of all THREE ring
    # schedules to fused_ring
    import dataclasses as _dc

    def _production(c, k=2048, m=8192, n=8192):
        if not c.fused:
            return _dc.replace(c, nbytes=c.nbytes * 512)
        kk = c.mm_k * -(-k // c.mm_k)
        mm = c.mm_m * -(-m // c.mm_m)
        nn = c.mm_n * -(-n // c.mm_n)
        it = c.itemsize
        nb = {"gather": (mm // c.p) * kk * it,
              "scatter": mm * kk * it,
              "contract": (kk // c.p) * nn * it}[c.mm_role]
        return _dc.replace(c, mm_k=kk, mm_m=mm, mm_n=nn, nbytes=nb)

    scaled = Trace([TraceEntry(_production(e.cell), e.phase, e.impl,
                               e.count) for e in trace.entries])
    rep = tuner.tune_trace(scaled,
                           backend=tuner.CostModelBackend(cm.V5E_ICI))
    # the overlap-ring family: fused_ring plus its quantized-wire variants
    # (wire_q8/wire_fp8 run the same ring schedule with an 8-bit wire and
    # may legitimately out-model fused_ring on comm-bound cells)
    ring_family = ("fused_ring", "wire_q8", "wire_fp8")
    fused = [
        (ph, prof.op, r.impl)
        for ph, store in rep.phase_profiles.items()
        for prof in store
        for r in prof.ranges
        if r.impl in ring_family
    ]
    assert any(op == "allgather_matmul" for _, op, _ in fused), fused
    assert any(op == "matmul_reducescatter" for _, op, _ in fused), fused
    assert any(op == "matmul_accumulate" for _, op, _ in fused), fused
    # the emitted profiles are geometry-keyed — the cells above must
    # resolve through lookup_cell at dispatch
    ph, store = next((ph, s) for ph, s in rep.phase_profiles.items()
                     for p_ in s if p_.op == "allgather_matmul")
    agmm_cells = [c for c, _cnt in Trace(scaled.entries).cells(ph).items()
                  if c.op == "allgather_matmul"]
    assert any(store.lookup_cell(c) in ring_family for c in agmm_cells)


# ---------------------------------------------------------------------------
# measured-backend trace replay: p-mismatch cells skip with a note
# ---------------------------------------------------------------------------


def test_tune_trace_measured_backend_skips_foreign_axis_sizes():
    t = Trace([TraceEntry.of("allreduce", 4, 1024, "fwd", "default", 3)])
    backend = tuner.MeasuredBackend()
    # this process sees 1 host device -> p=4 cells cannot be replayed
    assert backend.supported_axis_size == 1
    rep = tuner.tune_trace(t, backend=backend)
    assert rep.phase_profiles == {}
    assert any("p=4 != host axis size" in n for n in rep.notes)
    assert rep.measurements == []


def test_tune_measured_backend_refuses_foreign_axis_size():
    rep = tuner.tune(ops=["allreduce"], sizes=(64,), axis_size=16,
                     backend=tuner.MeasuredBackend())
    assert len(rep.profiles) == 0
    assert any("host axis size" in n for n in rep.notes)


# ---------------------------------------------------------------------------
# dispatch fast path
# ---------------------------------------------------------------------------


def test_fast_path_records_and_selects_default(rng):
    x = jnp.ones((4, 8), jnp.float32)
    with api.tuned() as ctx:
        jax.vmap(lambda a: api.allreduce(a, "x"), axis_name="x")(x)
    assert [tuple(r) for r in ctx.record] == \
        [("allreduce", 4, 32, "default", "fwd")]
    assert ctx.record[0].cell.dtype == "float32"


def test_fast_path_defers_to_profiles_and_env(monkeypatch):
    from repro.core.profiles import Profile, ProfileStore, Range
    x = jnp.ones((4, 8), jnp.float32)
    store = ProfileStore([Profile(op="allreduce", axis_size=4,
                                  ranges=[Range(1, 10 ** 6,
                                                "allreduce_as_doubling")])])
    with api.tuned(profiles=store) as ctx:
        jax.vmap(lambda a: api.allreduce(a, "x"), axis_name="x")(x)
    assert ctx.record[0].impl == "allreduce_as_doubling"
    monkeypatch.setenv("PGTUNE_MODULE", "allreduce:alg=allreduce_as_doubling")
    with api.tuned() as ctx2:
        jax.vmap(lambda a: api.allreduce(a, "x"), axis_name="x")(x)
    assert ctx2.record[0].impl == "allreduce_as_doubling"
