"""Fleet-scale online retuning: shard recording, epochal profiles and
the store-ref hot swap.

Covers the fleet loop at unit scale: bounded per-server
``ShardRecorder``s, weight-preserving ``Trace.merge_shards``, MANIFEST
epochs with the staleness rule, the dispatcher's selection order, and a
swapped epoch reaching the next trace of a step (an executable already
compiled keeps the impl it was traced with).
"""
import json
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import api, collectives as C, tuner
from repro.core.cell import OpCell
from repro.core.profiles import (MANIFEST_NAME, Profile, ProfileStore,
                                 Range, StoreRef, read_manifest,
                                 resolve_stores, write_manifest)
from repro.core.trace import (ShardRecorder, Trace, TraceEntry,
                              load_shard_latencies, shard_digest,
                              shard_meta)
from repro.core.tuner import FeedbackBackend, estimate_trace_cost


# ---------------------------------------------------------------------------
# ShardRecorder: bounded sampling across recompilations
# ---------------------------------------------------------------------------


def _rec(op="allreduce", p=4, nbytes=512, impl="default", phase="fwd"):
    return api.DispatchRecord(OpCell(op, p, nbytes), impl, phase)


def test_shard_recorder_aggregates_and_accepts_both_record_shapes():
    r = ShardRecorder("srv0")
    r.append(_rec())
    r.append(_rec())
    r.append(("allreduce", 4, 512, "default", "fwd"))   # legacy 5-tuple
    r.append(_rec(phase="bwd"))
    assert len(r) == 2
    assert r.total() == 4
    assert r.trace().cells() == {OpCell("allreduce", 4, 512): 4}


def test_shard_recorder_bounds_distinct_cells_and_accounts_drops():
    r = ShardRecorder("srv0", max_cells=4, seed=7)
    for i in range(50):
        r.append(_rec(nbytes=8 * (i + 1)))
    assert len(r) <= 4
    # every dispatch is either held in a cell count or accounted dropped
    assert r.total() + r.dropped == 50
    # held counts stay exact: re-dispatching a held cell never drops
    held = next(iter(r.trace().cells()))
    before = r.total()
    r.append(_rec(nbytes=held.nbytes))
    assert r.total() == before + 1


def test_shard_recorder_flush_writes_header_and_resets(tmp_path):
    r = ShardRecorder("srv3")
    for _ in range(5):
        r.append(_rec())
    r.observe(OpCell("allreduce", 4, 512), "allreduce_as_doubling", 1e-4)
    path = r.flush(tmp_path, epoch=2)
    assert path.name == "shard-srv3-e000002.jsonl"
    meta = shard_meta(path)
    assert meta["server"] == "srv3" and meta["epoch"] == 2
    assert meta["dispatches"] == 5 and meta["dropped"] == 0
    # comment-prefixed header/#@lat lines are invisible to Trace parsers
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        t = Trace.load(path)
    assert t.total() == 5
    # flush resets the window — the next epoch starts empty
    assert len(r) == 0 and r.total() == 0 and r.dropped == 0


def test_latency_reservoir_bounds_samples_keeps_observed_count(tmp_path):
    r = ShardRecorder("srv0", reservoir=8, seed=1)
    cell = OpCell("allreduce", 4, 512)
    for i in range(100):
        r.observe(cell, "allreduce_as_doubling", 1e-6 * (i + 1))
    r.append(_rec())
    path = r.flush(tmp_path, epoch=1)
    lat_lines = [ln for ln in path.read_text().splitlines()
                 if ln.startswith("#@lat ")]
    assert len(lat_lines) == 1
    m = json.loads(lat_lines[0][len("#@lat "):])
    assert len(m["lat_s"]) == 8            # reservoir bound
    assert m["observed"] == 100            # true sample count preserved
    back = load_shard_latencies(tmp_path)
    assert len(back[(cell, "allreduce_as_doubling")]) == 8


# ---------------------------------------------------------------------------
# merge_shards: fleet weight conservation
# ---------------------------------------------------------------------------


def test_merge_shards_preserves_total_weight(tmp_path):
    recs = [ShardRecorder(f"srv{i}") for i in range(3)]
    for i, r in enumerate(recs):
        for j in range(i + 1):
            r.append(_rec(nbytes=256 * (j + 1)))
            r.append(_rec(op="allgather", phase="decode"))
        r.flush(tmp_path, epoch=1)
    report = Trace.merge_shards(tmp_path)
    merged = report.trace
    assert len(report.merged) == 3 and not report.quarantined
    assert merged.total() == sum(i + 1 for i in range(3)) * 2
    assert merged.cells(phase="decode") == {OpCell("allgather", 4, 512): 6}


def test_merge_shards_empty_directory_warns_empty_report(tmp_path):
    # a cold-started fleet's first merge is a no-op, not a crash (the
    # old behavior raised FileNotFoundError); absent dir same deal
    with pytest.warns(UserWarning, match="cold start"):
        report = Trace.merge_shards(tmp_path)
    assert report.trace.total() == 0 and not report.shards
    with pytest.warns(UserWarning, match="no trace shards"):
        report = Trace.merge_shards(tmp_path / "never-created")
    assert report.trace.total() == 0 and len(report) == 0


def test_shard_digest_tracks_content(tmp_path):
    r = ShardRecorder("a")
    r.append(_rec())
    r.flush(tmp_path, epoch=1)
    d1 = shard_digest(tmp_path)
    assert d1.startswith("sha256:")
    r.append(_rec(nbytes=4096))
    r.flush(tmp_path, epoch=2)
    assert shard_digest(tmp_path) != d1


# ---------------------------------------------------------------------------
# MANIFEST + epochs
# ---------------------------------------------------------------------------


def _store(impl="allreduce_as_doubling", lo=1, hi=1 << 20):
    return ProfileStore([Profile(op="allreduce", axis_size=4,
                                 ranges=[Range(lo, hi, impl)])])


def test_manifest_roundtrip_with_census(tmp_path):
    write_manifest(tmp_path, 3, source_digest="sha256:abc",
                   base=_store(), phases={"decode": _store()})
    man = read_manifest(tmp_path)
    assert man["epoch"] == 3
    assert man["source"] == "sha256:abc"
    assert man["phases"] == {"decode": 1}
    assert man["geometry_census"]["allreduce"]["profiles"] == 2


def test_profile_store_save_with_epoch_writes_manifest(tmp_path):
    _store().save(tmp_path, epoch=5, source_digest="sha256:xyz")
    man = read_manifest(tmp_path)
    assert man["epoch"] == 5 and man["source"] == "sha256:xyz"
    # the MANIFEST must not be mistaken for a JSON profile on re-load
    back = ProfileStore.load(tmp_path)
    assert len(back) == 1


def test_manifest_demotions_roundtrip_through_poll(tmp_path):
    """The publishing process's demotion ledger rides MANIFEST.json and is
    re-applied when a FRESH process (empty ledger) adopts the epoch — a
    generation tuned with a wire impl excluded must not be served by a
    process that would route traffic back onto it."""
    C.clear_demotions()
    try:
        C.demote("allreduce", "wire_q8", "tolerance rel=0.5 > 0.063")
        _store().save(tmp_path, epoch=3)          # ledger snapshot rides along
        man = read_manifest(tmp_path)
        assert man["demotions"] == \
            [["allreduce", "wire_q8", "tolerance rel=0.5 > 0.063"]]

        C.clear_demotions()                       # the fresh serving process
        assert not C.is_demoted("allreduce", "wire_q8")
        ref = StoreRef(directory=tmp_path)
        assert ref.poll() and ref.epoch == 3
        assert C.is_demoted("allreduce", "wire_q8")
        reason = C.demotions()[("allreduce", "wire_q8")]
        assert reason.startswith("manifest: ")    # provenance is visible
    finally:
        C.clear_demotions()


def test_manifest_demotions_explicit_and_unknown_rows(tmp_path):
    """``demotions=`` overrides the ambient ledger; a row naming an impl
    this build doesn't know (a manifest from a newer build) is skipped
    with a warning, never fatal, and the rest still apply."""
    C.clear_demotions()
    try:
        _store().save(tmp_path)
        write_manifest(tmp_path, 4, base=_store(),
                       demotions={("allreduce", "wire_fp8"): "tol",
                                  ("allreduce", "no_such_impl"): "tol"})
        assert len(read_manifest(tmp_path)["demotions"]) == 2
        ref = StoreRef(directory=tmp_path)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            assert ref.poll()
        assert any("no_such_impl" in str(w.message) for w in caught)
        assert C.is_demoted("allreduce", "wire_fp8")
        assert not C.is_demoted("allreduce", "wire_q8")
    finally:
        C.clear_demotions()


def test_trace_tune_report_save_with_epoch(tmp_path):
    rep = tuner.TraceTuneReport(
        phase_profiles={"decode": _store()}, measurements=[],
        est_default_s={"decode": 1.0}, est_tuned_s={"decode": 0.5})
    rep.save(tmp_path, epoch=7, source_digest="sha256:s")
    man = read_manifest(tmp_path)
    assert man["epoch"] == 7 and man["phases"] == {"decode": 1}
    assert (tmp_path / "decode").is_dir()


# ---------------------------------------------------------------------------
# StoreRef: atomic swap, staleness, watch/poll
# ---------------------------------------------------------------------------


def test_store_ref_lookup_phase_over_base():
    ref = StoreRef(base=_store("implBase"),
                   phases={"decode": _store("implDecode")}, epoch=0)
    cell = OpCell("allreduce", 4, 512)
    assert ref.lookup(cell, "decode") == "implDecode"
    assert ref.lookup(cell, "prefill") == "implBase"


def test_store_ref_swap_refuses_stale_epoch():
    ref = StoreRef(base=_store("implA"), epoch=4)
    with pytest.warns(UserWarning, match="stale"):
        assert not ref.swap(_store("implB"), None, 3)
    assert ref.epoch == 4
    assert ref.lookup(OpCell("allreduce", 4, 512), "fwd") == "implA"
    assert not ref.swap(_store("implB"), None, 4)    # same epoch: no-op
    assert ref.swap(_store("implB"), None, 5)
    assert ref.lookup(OpCell("allreduce", 4, 512), "fwd") == "implB"


def test_store_ref_poll_adopts_new_epoch_and_refuses_regression(tmp_path):
    ref = StoreRef(directory=tmp_path)
    assert not ref.poll()                  # empty dir: nothing to adopt
    _store("implA").save(tmp_path, epoch=1)
    assert ref.poll()
    assert ref.epoch == 1
    assert ref.lookup(OpCell("allreduce", 4, 512), "fwd") == "implA"
    assert not ref.poll()                  # unchanged manifest: no re-stat
    # a delayed writer regressing the manifest must be refused
    write_manifest(tmp_path, 0)
    with pytest.warns(UserWarning, match="stale"):
        assert not ref.poll()
    assert ref.epoch == 1
    # a newer epoch lands: adopted
    _store("implB").save(tmp_path, epoch=2)
    assert ref.poll()
    assert ref.epoch == 2
    assert ref.lookup(OpCell("allreduce", 4, 512), "fwd") == "implB"


def test_store_ref_poll_adopts_legacy_manifestless_dir_once(tmp_path):
    _store("implA").save(tmp_path)         # no epoch, no MANIFEST
    ref = StoreRef(directory=tmp_path)
    assert ref.poll()
    assert ref.epoch == 0
    assert not ref.poll()                  # adopted once, not re-adopted


def test_resolve_stores_watch_mode_returns_ref(tmp_path, monkeypatch):
    _store("implA").save(tmp_path, epoch=4)
    monkeypatch.setenv("PGTUNE_PROFILE_DIR", str(tmp_path))
    ref = resolve_stores(watch=True)
    assert isinstance(ref, StoreRef)
    assert ref.epoch == 4                  # first poll happens at resolve
    assert ref.lookup(OpCell("allreduce", 4, 512), "fwd") == "implA"


def test_resolve_stores_watch_mode_unset_env(monkeypatch):
    monkeypatch.delenv("PGTUNE_PROFILE_DIR", raising=False)
    ref = resolve_stores(watch=True)
    assert ref.epoch == -1 and not ref.poll()


# ---------------------------------------------------------------------------
# dispatch: selection order, and a new epoch reaching the next trace
# ---------------------------------------------------------------------------


P = 4
A, B = "allreduce_as_doubling", "allreduce_as_rsb_allgather"


@pytest.mark.parametrize("winner,loser", [
    ("impl", "force"),
    ("env", "phase_profiles"),
    ("profiles", "store_ref"),
])
def test_select_precedence(monkeypatch, winner, loser):
    """Of two sources that both name an impl, the earlier one in the
    selection order wins; without it, the later one is what dispatches
    (so the loser was live, not ignored)."""
    def selected(sources):
        monkeypatch.delenv("PGTUNE_MODULE", raising=False)
        kw = {}
        for src, name in sources.items():
            if src == "force":
                kw["force"] = {"allreduce": name}
            elif src == "env":
                monkeypatch.setenv("PGTUNE_MODULE", f"allreduce:alg={name}")
            elif src == "phase_profiles":
                kw["phase_profiles"] = {api.DEFAULT_PHASE: _store(name)}
            elif src == "profiles":
                kw["profiles"] = _store(name)
            elif src == "store_ref":
                kw["store_ref"] = StoreRef(base=_store(name), epoch=0)
        impl = sources.get("impl")
        with api.tuned(**kw) as ctx:
            jax.vmap(lambda a: api.allreduce(a, "ax", impl=impl),
                     axis_name="ax")(jnp.ones((P, 4), jnp.float32))
        return [r.impl for r in ctx.record]

    assert selected({winner: A, loser: B}) == [A]
    assert selected({loser: B}) == [B]


@pytest.fixture
def probe_impl(monkeypatch):
    """A marker impl whose output is distinguishable from any real
    allreduce — proof of which impl a compiled step RUNS."""
    probe = C.Impl(name="probe_marker", op="allreduce",
                   fn=lambda x, axis, **kw: jnp.full_like(x, 42.0),
                   guideline="EXT", extra_bytes=lambda n, p: 0)
    monkeypatch.setitem(C.REGISTRY["allreduce"], "probe_marker", probe)
    return probe


def test_store_ref_epoch_reaches_the_next_trace(probe_impl):
    """Dispatch reads the ref at trace time: a step compiled before the
    swap keeps its impl, and a fresh trace selects and records the new
    epoch's."""
    ref = StoreRef()

    def step(x):
        return api.allreduce(x, "ax")

    x = jnp.ones((P, 4), jnp.float32)
    with api.tuned(store_ref=ref) as ctx:
        f = jax.jit(jax.vmap(step, axis_name="ax"))
        np.testing.assert_allclose(f(x), np.full((P, 4), float(P)))
        assert ref.swap(_store("probe_marker"), None, epoch=1)
        np.testing.assert_allclose(f(x), np.full((P, 4), float(P)))
        assert f._cache_size() == 1
        g = jax.jit(jax.vmap(step, axis_name="ax"))
        np.testing.assert_allclose(g(x), np.full((P, 4), 42.0))
    assert [r.impl for r in ctx.record] == ["default", "probe_marker"]


# ---------------------------------------------------------------------------
# feedback: fleet measurements drive the next epoch
# ---------------------------------------------------------------------------


def test_feedback_backend_overrides_with_observed_median():
    from repro.core import costmodel
    base = tuner.CostModelBackend(costmodel.V5E_ICI)
    cell = OpCell("allreduce", 4, 4096)
    obs = {(cell, "default"): [3e-6, 1e-6, 2e-6]}
    fb = FeedbackBackend(base, obs, min_samples=3)
    assert fb.latency(cell, "default") == 2e-6            # median
    assert fb.nrep_for(cell, "default") == 3
    # under-sampled pairs and unseen cells fall back to the base model
    fb2 = FeedbackBackend(base, obs, min_samples=5)
    assert fb2.latency(cell, "default") == base.latency(cell, "default")
    other = OpCell("allreduce", 4, 128)
    assert fb.latency(other, "default") == base.latency(other, "default")


def test_estimate_trace_cost_prices_profile_selection():
    from repro.core import costmodel
    backend = tuner.CostModelBackend(costmodel.V5E_ICI)
    t = Trace([TraceEntry.of("allreduce", 16, 1 << 20, "decode", count=10)])
    untuned = estimate_trace_cost(t, backend)
    rep = tuner.tune_trace(t, backend=backend)
    tuned = estimate_trace_cost(t, backend, phases=rep.phase_profiles)
    assert set(untuned) == {"decode"}
    if rep.phase_profiles:                 # a violation was found
        assert tuned["decode"] < untuned["decode"]
    # an inadmissible selection silently degrades to the default price
    bad = {"decode": _store("not_an_impl", hi=1 << 30)}
    cell16 = Trace([TraceEntry.of("allreduce", 16, 1 << 20, "decode")])
    assert (estimate_trace_cost(cell16, backend, phases=bad)["decode"]
            == estimate_trace_cost(cell16, backend)["decode"])
