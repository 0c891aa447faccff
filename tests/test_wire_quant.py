"""Quantized-wire admissibility: the tolerance gate, the demotion ledger,
and the dtype threading that makes both possible.

Satellite coverage for the wire_q8/wire_fp8 mock-up family:

* selfcheck.run_gate demotes a wire impl on an adversarial payload the wire
  format cannot represent (large in-block dynamic range / cancellation),
  and passes it on benign payloads;
* a demoted impl disappears from every selection surface — dispatch
  (api._select falls back to default, whichever source named the impl)
  and the tuner (never selected, cost estimates fall back to default);
* OpCell.dtype round-trips dispatch -> trace JSONL -> geometry profile key
  -> lookup_cell (regression for the dtype-threading audit: a bfloat16
  callsite must not come back as float32).
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import api, collectives as C, costmodel, selfcheck, tuner
from repro.core.profiles import Profile, ProfileStore, Range, StoreRef
from repro.core.trace import Trace, TraceEntry
from repro.kernels.quant import wire_tol

P = 4


@pytest.fixture(autouse=True)
def _clean_ledger():
    C.clear_demotions()
    yield
    C.clear_demotions()


def _cancellation_payload(p=P, n=16, d=4, scale=1e3):
    """Shards with large magnitudes that sum to nearly zero: the allreduce
    answer is O(1) but every wire hop quantizes O(scale) values, so the
    absolute quantization error (~scale/254 per hop for int8) dwarfs the
    true result — exactly the payload class the tolerance gate exists for."""
    rng = np.random.default_rng(7)
    tiny = rng.normal(size=(p, n, d)).astype(np.float32)
    x = tiny.copy()
    x[0] += scale
    x[1] -= scale
    return x


# ---------------------------------------------------------------------------
# tolerance gate -> demotion
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["wire_q8", "wire_fp8"])
def test_selfcheck_gate_demotes_wire_on_cancellation(name):
    ok, rel, tol = selfcheck.run_gate("allreduce", name,
                                      _cancellation_payload())
    assert not ok
    assert rel > tol
    assert C.is_demoted("allreduce", name)
    assert ("allreduce", name) in C.demotions()


def test_selfcheck_gate_passes_wire_on_benign_payload():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(P, 16, 4)).astype(np.float32)
    ok, rel, tol = selfcheck.run_gate("allreduce", "wire_q8", x)
    assert ok
    assert rel <= tol == wire_tol("int8", selfcheck.wire_hops("allreduce", P))
    assert not C.is_demoted("allreduce", "wire_q8")


def test_selfcheck_gate_demote_false_only_reports():
    ok, _, _ = selfcheck.run_gate("allreduce", "wire_q8",
                                  _cancellation_payload(), demote=False)
    assert not ok
    assert not C.is_demoted("allreduce", "wire_q8")


def test_default_impl_cannot_be_demoted():
    with pytest.raises(ValueError):
        C.demote("allreduce", "default")
    with pytest.raises(KeyError):
        C.demote("allreduce", "no_such_impl")


# ---------------------------------------------------------------------------
# demotion is respected everywhere an impl can be chosen
# ---------------------------------------------------------------------------


def _wire_q8_store():
    return ProfileStore([Profile(op="allreduce", axis_size=P,
                                 ranges=[Range(1, 1 << 20, "wire_q8")])])


@pytest.mark.parametrize("source,name,served", [
    ("force", "wire_q8", "default"),
    ("profiles", "wire_q8", "default"),
    ("store_ref", "wire_q8", "default"),
    ("force", "wire_fp8", "wire_fp8"),      # only the breaker goes
])
def test_demoted_impl_falls_back_to_default_in_dispatch(source, name,
                                                         served):
    C.demote("allreduce", "wire_q8", "tolerance")
    tuned_kw = {
        "force": lambda: {"force": {"allreduce": name}},
        "profiles": lambda: {"profiles": _wire_q8_store()},
        "store_ref": lambda: {"store_ref": StoreRef(base=_wire_q8_store(),
                                                    epoch=0)},
    }[source]()
    x = jnp.asarray(np.random.default_rng(0).normal(size=(P, 8, 4)),
                    jnp.float32)
    with api.tuned(**tuned_kw) as ctx:
        got = jax.vmap(lambda a: api.allreduce(a, "x"), axis_name="x")(x)
    assert [r.impl for r in ctx.record] == [served]
    want = np.broadcast_to(np.asarray(x).sum(0), x.shape)
    if served == "default":
        # the selected-but-demoted impl was swapped for default: exact
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    else:
        wd = C.REGISTRY["allreduce"][served].wire_dtype
        assert selfcheck.rel_err(got, want) <= wire_tol(
            wd, selfcheck.wire_hops("allreduce", P))


def test_tuner_never_selects_demoted_wire_impls():
    """On a comm-bound DCN cell the wire family wins by construction; after
    demoting both wire impls the tuner must re-select from the rest."""
    t = Trace([TraceEntry.of("allreduce", 8, 4 << 20)])
    backend = tuner.CostModelBackend(costmodel.V5E_DCN)

    rep = tuner.tune_trace(t, backend=backend)
    sel = rep.phase_profiles["fwd"].lookup("allreduce", 8, 4 << 20)
    assert sel in ("wire_q8", "wire_fp8")

    C.demote("allreduce", "wire_q8", "tolerance")
    C.demote("allreduce", "wire_fp8", "tolerance")
    rep2 = tuner.tune_trace(t, backend=backend)
    store2 = rep2.phase_profiles.get("fwd")
    sel2 = store2.lookup("allreduce", 8, 4 << 20) if store2 else None
    assert sel2 not in ("wire_q8", "wire_fp8")   # None or a non-wire winner

    # cost estimation prices the (stale) wire selection as default, never
    # the demoted impl's cheaper wire latency
    est = tuner.estimate_trace_cost(t, backend, phases=rep.phase_profiles)
    est_def = tuner.estimate_trace_cost(t, backend)
    assert est["fwd"] == pytest.approx(est_def["fwd"])


# ---------------------------------------------------------------------------
# dtype threading regression (satellite 1)
# ---------------------------------------------------------------------------


def test_non_f32_dispatch_roundtrips_dtype_to_profile_lookup():
    """bfloat16 fused callsite -> recorded cell -> JSONL -> geometry profile
    keyed on dtype -> lookup_cell resolves for bf16 and (correctly) NOT for
    an identical f32 cell."""
    n, k, m = 256, 512, 64
    x = jnp.ones((P, n, k), jnp.bfloat16)
    w = jnp.ones((k, m), jnp.bfloat16)
    with api.tuned() as ctx:
        jax.vmap(lambda a: api.allgather_matmul(a, w, "x"),
                 axis_name="x")(x)
    t = Trace.from_context(ctx)
    (cell,) = t.cells().keys()
    assert cell.dtype == "bfloat16"
    assert cell.fused and cell.nbytes == n * k * 2

    back = Trace.from_jsonl(t.to_jsonl())
    assert back == t
    (bcell,) = back.cells().keys()
    assert bcell.dtype == "bfloat16"
    assert bcell.geom() is not None and bcell.geom().dtype == "bfloat16"

    rep = tuner.tune_trace(back,
                           backend=tuner.CostModelBackend(costmodel.V5E_DCN))
    store = rep.phase_profiles["fwd"]
    sel = store.lookup_cell(bcell)
    assert sel is not None                       # tuned under the bf16 key
    f32_twin = dataclasses.replace(bcell, dtype="float32")
    assert store.lookup_cell(f32_twin) is None   # dtype is part of the key


# ---------------------------------------------------------------------------
# wire_hops audit: count error-ADDING quantization events, not ring hops
# ---------------------------------------------------------------------------

PA = 8           # accumulate-audit axis size
K_LOC, M_A, T_A = 8, 16, 4


def test_wire_hops_counts_error_adding_events():
    """The tolerance multiplier is the number of independently-quantized
    error terms that can ADD into one output element — NOT the number of
    times the travelling payload crosses the wire."""
    # gather-style: each block quantized once at its origin, errors never meet
    assert selfcheck.wire_hops("allgather", PA) == 1
    assert selfcheck.wire_hops("allgather_matmul", PA) == 1
    # travelling accumulators: p-1 requantized partial sums
    assert selfcheck.wire_hops("reducescatter", PA) == PA - 1
    assert selfcheck.wire_hops("matmul_reducescatter", PA) == PA - 1
    # allreduce = RS (p-1 requantizes) + the AG-phase re-quantize on top
    assert selfcheck.wire_hops("allreduce", PA) == PA
    # matmul_accumulate streams blocks quantized ONCE each, but the
    # stationary-x contraction sums all p-1 wire-crossed blocks' errors
    # into every output element (the audited fix: the old travelling-data
    # rule said 1)
    assert selfcheck.wire_hops("matmul_accumulate", PA) == PA - 1
    # a 2-D cell's budget comes from its INNER reduction ring of size p2
    assert selfcheck.wire_hops("matmul_reducescatter_2d", PA, 4) == 3
    assert selfcheck.wire_hops("matmul_reducescatter_2d", PA) == PA - 1
    # degenerate axes never multiply below the single-roundtrip base
    for op in ("reducescatter", "allreduce", "matmul_accumulate"):
        assert selfcheck.wire_hops(op, 1) == 1
    # the multiplier is monotone in the tolerance it produces
    assert wire_tol("int8", PA - 1) == (PA - 1) * wire_tol("int8", 1)


def _accumulate_payload(gamma, seed=11, p=PA):
    """Stacked weight K-blocks [p, k_loc, m] + stationary x [T, K].

    Weight columns are near-constant with sub-quantization-step dither, so
    each block's int8 rounding residuals are independent k-varying noise
    (NO in-block dynamic-range abuse — every value sits in [1, 2]); the
    stationary rows have their sum suppressed by ``gamma``, so the true
    output shrinks with gamma while the p-1 accumulated per-block errors
    random-walk undiminished.  gamma=0.1 lands the relative error ABOVE
    the single-roundtrip bound but UNDER the (p-1)-event bound; gamma=0
    is the full-cancellation adversarial payload.
    """
    rng = np.random.default_rng(seed)
    K = p * K_LOC
    c = rng.uniform(1.0, 2.0, size=(1, M_A))
    dither = rng.uniform(-0.004, 0.004, size=(K, M_A))
    wblocks = (np.broadcast_to(c, (K, M_A)) + dither).astype(
        np.float32).reshape(p, K_LOC, M_A)
    z = rng.normal(size=(T_A, K))
    xstat = (z - (1.0 - gamma) * z.mean(axis=1, keepdims=True)).astype(
        np.float32)
    return wblocks, xstat


def test_accumulate_error_adding_payload_needs_p_minus_1_events():
    """Regression for the hops audit: a benign error-ADDING payload whose
    measured error exceeds the old hops=1 bound (spurious demotion on
    HEAD) but sits inside the corrected (p-1)-event budget."""
    wb, xs = _accumulate_payload(gamma=0.1)
    ok, rel, tol = selfcheck.run_gate("matmul_accumulate", "wire_q8",
                                      wb, w=xs)
    assert rel > wire_tol("int8", 1)       # the old bound would demote this
    assert ok and rel <= tol == wire_tol("int8", PA - 1)
    assert not C.is_demoted("matmul_accumulate", "wire_q8")


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 1), st.integers(0, 10 ** 6))
def test_accumulate_benign_payloads_never_demote(wd_i, seed):
    """Property: random normal payloads stay under the (p-1)-event bound
    for both wire dtypes — the gate never spuriously demotes."""
    name = ("wire_q8", "wire_fp8")[wd_i]
    rng = np.random.default_rng(seed)
    wb = rng.normal(size=(PA, K_LOC, M_A)).astype(np.float32)
    xs = rng.normal(size=(T_A, PA * K_LOC)).astype(np.float32)
    C.clear_demotions()
    ok, rel, tol = selfcheck.run_gate("matmul_accumulate", name, wb, w=xs)
    assert ok and rel <= tol
    assert not C.is_demoted("matmul_accumulate", name)


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 1), st.integers(0, 10 ** 6))
def test_accumulate_adversarial_cancellation_always_fires(wd_i, seed):
    """Property: on full-cancellation payloads the measured error exceeds
    even the widened (p-1)-event bound — the gate bound is never looser
    than the error the payload class actually produces, so widening the
    multiplier did not open a demotion hole."""
    name = ("wire_q8", "wire_fp8")[wd_i]
    wb, xs = _accumulate_payload(gamma=0.0, seed=seed)
    C.clear_demotions()
    ok, rel, tol = selfcheck.run_gate("matmul_accumulate", name, wb, w=xs)
    assert not ok and rel > tol
    assert C.is_demoted("matmul_accumulate", name)
