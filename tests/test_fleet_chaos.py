"""Fault-tolerant fleet retuning (ISSUE 8): shard quarantine with exact
weight accounting, epoch history/rollback/poisoning, the content-based
poll stamp, manifest↔profile digest verification, the EpochTripwire,
MAD-robust feedback statistics, and the drift/failure coordinator.

Everything here is deterministic: injected faults come from a seeded
``ft.ChaosMonkey``, liveness from an injected fake clock.
"""
import json
import os
import warnings

import pytest

from repro.core import api
from repro.core.cell import OpCell
from repro.core.profiles import (MANIFEST_NAME, EpochTripwire, Profile,
                                 ProfileStore, Range, StoreRef,
                                 profiles_digest, read_manifest,
                                 write_manifest)
from repro.core.trace import (ShardRecorder, Trace, load_shard_latencies,
                              shard_meta)
from repro.core.tuner import CostModelBackend, FeedbackBackend, _mad_filter
from repro.core import costmodel
from repro.ft import ChaosMonkey, FleetCoordinator


IMPL = "allreduce_as_rsb_allgather"       # a registered allreduce mock-up


def _rec(op="allreduce", p=4, nbytes=512, impl="default", phase="fwd"):
    return api.DispatchRecord(OpCell(op, p, nbytes), impl, phase)


def _flush_shard(tmp_path, server="srv0", epoch=1, n=6, obs=None,
                 seed=0):
    r = ShardRecorder(server, seed=seed)
    for i in range(n):
        r.append(_rec(nbytes=256 * (1 + i % 2)))
    for lat in obs or []:
        r.observe(OpCell("allreduce", 4, 512), IMPL, lat)
    return r.flush(tmp_path, epoch=epoch)


def _store(impl=IMPL):
    return ProfileStore([Profile("allreduce", 4,
                                 [Range(0, 1 << 30, impl)])])


# ---------------------------------------------------------------------------
# quarantine: every chaos fault lands in the right bucket, weight exact
# ---------------------------------------------------------------------------


def test_torn_shard_quarantined_with_digest_mismatch(tmp_path):
    good = _flush_shard(tmp_path, "srv0", n=4)
    torn = _flush_shard(tmp_path, "srv1", n=6)
    ChaosMonkey(seed=1).tear_shard(torn, keep_frac=0.4)
    with pytest.warns(UserWarning, match="quarantined"):
        report = Trace.merge_shards(tmp_path)
    assert [n.path for n in report.merged] == [good]
    (q,) = report.quarantined
    assert q.path == torn and "digest-mismatch" in q.reason
    assert q.claimed == 6 and q.dropped == 6
    # merged weight == surviving shards' weight, exactly
    assert report.trace.total() == 4
    assert report.dropped_weight == 6


def test_corrupt_line_quarantined_as_parse_error(tmp_path):
    p = _flush_shard(tmp_path, "srv0", n=4)
    ChaosMonkey(seed=2).corrupt_line(p, line=0)
    # without digest verification the parse-error path must catch it
    with pytest.warns(UserWarning, match="quarantined"):
        report = Trace.merge_shards(tmp_path, verify_digest=False)
    (q,) = report.quarantined
    assert "parse-error" in q.reason
    assert q.claimed == 4
    # with digest verification the (earlier) digest check catches it
    with pytest.warns(UserWarning, match="quarantined"):
        report2 = Trace.merge_shards(tmp_path)
    assert "digest-mismatch" in report2.quarantined[0].reason


def test_header_corruption_and_meta_skew_quarantined(tmp_path):
    skewed = _flush_shard(tmp_path, "srv0", n=3)
    ChaosMonkey(seed=3).skew_header(skewed, epoch=9)
    broken = _flush_shard(tmp_path, "srv1", n=3)
    text = broken.read_text()
    broken.write_text("#@shard {not json" + text.partition("\n")[2])
    with pytest.warns(UserWarning, match="quarantined"):
        report = Trace.merge_shards(tmp_path)
    reasons = {n.path.name: n.reason for n in report.quarantined}
    assert "meta-skew" in reasons[skewed.name]
    assert "header-corrupt" in reasons[broken.name]
    assert report.trace.total() == 0


def test_salvaged_weight_accounted_never_merged(tmp_path):
    p = _flush_shard(tmp_path, "srv0", n=6)
    # drop the header's digest claim AND truncate: the claim is gone, so
    # accounting falls back to the parseable-prefix weight
    head, _sep, body = p.read_text().partition("\n")
    meta = json.loads(head[len("#@shard "):])
    del meta["dispatches"]
    lines = body.splitlines()
    p.write_text("#@shard " + json.dumps(meta) + "\n"
                 + "\n".join(lines[:1]) + "\ngarbage{{{\n")
    with pytest.warns(UserWarning, match="quarantined"):
        report = Trace.merge_shards(tmp_path, verify_digest=False)
    (q,) = report.quarantined
    assert q.claimed is None
    assert q.salvaged == 3            # the surviving first line's count
    assert q.dropped == 3
    assert report.trace.total() == 0  # salvage is accounting, not data


def test_headerless_legacy_file_still_merges(tmp_path):
    t = Trace([  # a plain v2 trace file dropped into the shard dir
        __import__("repro.core.trace", fromlist=["TraceEntry"])
        .TraceEntry.of("allreduce", 4, 512, count=7)])
    (tmp_path / "shard-legacy-e000001.jsonl").write_text(t.to_jsonl())
    report = Trace.merge_shards(tmp_path)
    assert report.trace.total() == 7
    (n,) = report.merged
    assert n.server is None and n.claimed is None


def test_quarantined_shard_latencies_skippable(tmp_path):
    good = _flush_shard(tmp_path, "srv0", obs=[1e-3, 1e-3], seed=0)
    bad = _flush_shard(tmp_path, "srv1", obs=[5e-2, 5e-2], seed=1)
    ChaosMonkey(seed=4).tear_shard(bad, keep_frac=0.9)
    with pytest.warns(UserWarning):
        report = Trace.merge_shards(tmp_path)
    obs = load_shard_latencies(
        tmp_path, skip=[n.path for n in report.quarantined])
    samples = obs[(OpCell("allreduce", 4, 512), IMPL)]
    assert samples == [1e-3, 1e-3]    # the torn shard's 5e-2 not trusted


# ---------------------------------------------------------------------------
# flush atomicity
# ---------------------------------------------------------------------------


def test_flush_leaves_no_tmp_and_digest_roundtrips(tmp_path):
    p = _flush_shard(tmp_path, "srv0", n=5, obs=[1e-3])
    assert not list(tmp_path.glob("*.tmp"))
    meta = shard_meta(p)
    assert meta["sha256"].startswith("sha256:")
    report = Trace.merge_shards(tmp_path)
    assert not report.quarantined and report.trace.total() == 5
    # one flipped byte in the body breaks the digest
    raw = p.read_bytes()
    p.write_bytes(raw[:-2] + b"X" + raw[-1:])
    with pytest.warns(UserWarning, match="digest-mismatch"):
        report = Trace.merge_shards(tmp_path)
    assert report.quarantined


# ---------------------------------------------------------------------------
# S1 regression: content-based poll stamp
# ---------------------------------------------------------------------------


def test_poll_adopts_same_size_same_mtime_manifest_replacement(tmp_path):
    """A manifest replaced by one of the SAME byte length and SAME mtime
    must still be adopted — the old ``(st_mtime_ns, st_size)`` stat
    stamp provably misses this (this test fails on pre-ISSUE-8 HEAD)."""
    _store().save(tmp_path, epoch=1)
    ref = StoreRef(directory=tmp_path)
    assert ref.poll() and ref.epoch == 1
    man = tmp_path / MANIFEST_NAME
    st = man.stat()
    text = man.read_text()
    assert '"epoch": 1' in text
    man.write_text(text.replace('"epoch": 1', '"epoch": 2'))
    assert man.stat().st_size == st.st_size
    os.utime(man, ns=(st.st_atime_ns, st.st_mtime_ns))
    assert man.stat().st_mtime_ns == st.st_mtime_ns
    assert ref.poll(), ("same-size same-mtime manifest replacement "
                        "missed by the poll stamp")
    assert ref.epoch == 2


def test_manifest_records_and_poll_verifies_profiles_digest(tmp_path):
    _store().save(tmp_path, epoch=1)
    man = read_manifest(tmp_path)
    assert man["profiles_digest"] == profiles_digest(tmp_path)
    ref = StoreRef(directory=tmp_path)
    assert ref.poll() and ref.epoch == 1
    # skew: profiles change after the manifest was written
    _store().save(tmp_path)
    write_manifest(tmp_path, 2)
    ChaosMonkey(seed=5).skew_profiles(tmp_path)
    with pytest.warns(UserWarning, match="skew"):
        assert not ref.poll()
    assert ref.epoch == 1
    # the skew persists: every poll re-checks (and re-warns) rather
    # than short-circuiting on the unchanged manifest
    with pytest.warns(UserWarning, match="skew"):
        assert not ref.poll()
    # repairing the PROFILES alone — manifest byte-identical — must be
    # enough to adopt; a stamp committed at refusal time would hide it
    _store().save(tmp_path)
    assert read_manifest(tmp_path)["profiles_digest"] \
        == profiles_digest(tmp_path)
    assert ref.poll() and ref.epoch == 2


# ---------------------------------------------------------------------------
# epoch history, rollback, poisoning
# ---------------------------------------------------------------------------


def test_storeref_retains_history_and_rolls_back():
    ref = StoreRef(history=2)
    for e in range(4):
        assert ref.swap(_store(), {}, e)
    assert ref.epoch == 3
    assert len(ref._history) == 2      # bounded retention
    with pytest.warns(UserWarning, match="rolled back"):
        assert ref.rollback() == 2
    with pytest.warns(UserWarning, match="rolled back"):
        assert ref.rollback() == 1
    with pytest.warns(UserWarning, match="no retained"):
        assert ref.rollback() is None
    assert ref.epoch == 1


def test_rolled_back_epoch_is_poisoned_for_swap_and_poll(tmp_path):
    _store().save(tmp_path, epoch=1)
    ref = StoreRef(directory=tmp_path)
    assert ref.poll()
    _store("allreduce_as_doubling").save(tmp_path, epoch=2)
    assert ref.poll() and ref.epoch == 2
    with pytest.warns(UserWarning, match="rolled back"):
        ref.rollback()
    assert ref.epoch == 1
    # the on-disk manifest still says epoch 2; poll must not re-adopt,
    # even when the manifest text changes (publisher retry)
    write_manifest(tmp_path, 2, source_digest="sha256:retry")
    with pytest.warns(UserWarning, match="poisoned"):
        assert not ref.poll()
    assert ref.epoch == 1
    with pytest.warns(UserWarning, match="poisoned"):
        assert not ref.swap(_store(), {}, 2)
    # a FRESH epoch recovers
    _store("allreduce_as_doubling").save(tmp_path, epoch=3)
    assert ref.poll() and ref.epoch == 3


def test_rollback_restores_lookup_results():
    cell = OpCell("allreduce", 4, 512)
    ref = StoreRef()
    ref.swap(_store(IMPL), {}, 1)
    ref.swap(_store("allreduce_as_doubling"), {}, 2)
    assert ref.lookup(cell, "fwd") == "allreduce_as_doubling"
    with pytest.warns(UserWarning):
        ref.rollback()
    assert ref.lookup(cell, "fwd") == IMPL


# ---------------------------------------------------------------------------
# EpochTripwire
# ---------------------------------------------------------------------------


def _tripwire_ref():
    ref = StoreRef()
    ref.swap(_store(IMPL), {}, 1)
    return ref


def test_tripwire_rolls_back_regressing_epoch():
    ref = _tripwire_ref()
    tw = EpochTripwire(ref, threshold=1.5, window=4, min_samples=3)
    for _ in range(4):
        assert not tw.observe(1.0)
    ref.swap(_store("allreduce_as_doubling"), {}, 2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert not tw.observe(2.0)     # below min_samples: no verdict yet
        assert not tw.observe(2.0)
        fired = tw.observe(2.0)        # median 2.0 > 1.5 x baseline 1.0
    assert fired
    assert tw.fired == [(2, 1)]
    assert ref.epoch == 1
    assert 1 in {e for e, *_ in [ref._state]}  # restored generation live


def test_tripwire_tolerates_single_spike_and_ok_epoch():
    ref = _tripwire_ref()
    tw = EpochTripwire(ref, threshold=1.5, window=5, min_samples=3)
    for _ in range(5):
        tw.observe(1.0)
    ref.swap(_store("allreduce_as_doubling"), {}, 2)
    # new epoch is FINE (1.1x); one 10x spike must not trip the median
    seq = [1.1, 1.1, 10.0, 1.1, 1.1, 1.1]
    assert not any(tw.observe(c) for c in seq)
    assert ref.epoch == 2 and not tw.fired


def test_tripwire_without_history_keeps_serving():
    ref = StoreRef()
    ref.swap(_store(), {}, 1)          # first epoch: nothing retained
    tw = EpochTripwire(ref, threshold=1.2, window=3, min_samples=2)
    tw._baseline = 1.0                 # pretend a prior epoch existed
    with pytest.warns(UserWarning, match="no retained"):
        fired = [tw.observe(5.0) for _ in range(3)]
    assert not any(fired) and ref.epoch == 1


# ---------------------------------------------------------------------------
# FeedbackBackend MAD rejection
# ---------------------------------------------------------------------------


def test_mad_filter_drops_spikes_keeps_tight_samples():
    assert _mad_filter([1.0, 1.1, 0.9, 1.05, 100.0], 4.0) == \
        [1.0, 1.1, 0.9, 1.05]
    # identical samples: the 5%-of-median floor keeps them all
    assert _mad_filter([2.0, 2.0, 2.0, 2.0], 4.0) == [2.0] * 4
    # tiny sets and k=0 pass through untouched
    assert _mad_filter([1.0, 50.0], 4.0) == [1.0, 50.0]
    assert _mad_filter([1.0, 1.0, 99.0], 0.0) == [1.0, 1.0, 99.0]


def test_feedback_backend_rejects_outliers_and_counts():
    cell = OpCell("allreduce", 4, 512)
    backend = CostModelBackend(costmodel.V5E_ICI)
    clean = [1e-3, 1.05e-3, 0.95e-3, 1e-3]
    fb = FeedbackBackend(backend, {(cell, IMPL): clean + [0.5]})
    assert fb.rejected == 1
    assert fb.latency(cell, IMPL) == pytest.approx(1e-3, rel=0.1)
    assert fb.nrep_for(cell, IMPL) == len(clean)
    # the spike would have dragged a plain median's neighbors; compare
    # against the unspiked backend: medians must agree exactly
    fb_clean = FeedbackBackend(backend, {(cell, IMPL): clean})
    assert fb.latency(cell, IMPL) == fb_clean.latency(cell, IMPL)
    # mad_k=0 disables rejection
    fb_off = FeedbackBackend(backend, {(cell, IMPL): clean + [0.5]},
                             mad_k=0)
    assert fb_off.rejected == 0


# ---------------------------------------------------------------------------
# ChaosMonkey determinism
# ---------------------------------------------------------------------------


def test_chaos_monkey_is_deterministic(tmp_path):
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    events = []
    for sub in ("a", "b"):
        p = _flush_shard(tmp_path / sub, "srv0", n=6, obs=[1e-3, 2e-3])
        m = ChaosMonkey(seed=42)
        m.tear_shard(p)
        m.spike_latencies(p, factor=10.0)
        m.kill_server("srv1", at_epoch=3)
        events.append([(e.kind, e.detail) for e in m.events])
    assert events[0] == events[1]
    m = ChaosMonkey(seed=42)
    m.kill_server("s", at_epoch=3)
    assert m.alive("s", 2) and not m.alive("s", 3)
    assert m.alive("other", 99)


# ---------------------------------------------------------------------------
# FleetCoordinator
# ---------------------------------------------------------------------------


def test_coordinator_flags_dead_and_straggler_servers(tmp_path):
    now = [0.0]
    ref = StoreRef(base=_store(), epoch=1)
    co = FleetCoordinator(tmp_path, ref, heartbeat_timeout=10.0,
                          straggler_epochs=1, clock=lambda: now[0])
    for s in ("s0", "s1", "s2"):
        _flush_shard(tmp_path, s, epoch=1)
    st = co.scan()
    assert st.alive == ["s0", "s1", "s2"] and not st.dead and not st.retune
    # s2 dies; s1 straggles at epoch 2 while s0 reaches epoch 4
    now[0] += 8.0
    _flush_shard(tmp_path, "s0", epoch=2)
    _flush_shard(tmp_path, "s1", epoch=2)
    now[0] += 8.0
    _flush_shard(tmp_path, "s0", epoch=3)
    now[0] += 1.0
    _flush_shard(tmp_path, "s0", epoch=4)
    st = co.scan()
    assert st.fleet_epoch == 4
    assert st.dead == ["s2"]
    assert st.stragglers == ["s1"]
    assert st.retune and any("dead" in r for r in st.reasons)
    assert "RETUNE" in st.summary()


def test_coordinator_drift_triggers_retune(tmp_path):
    now = [0.0]
    ref = StoreRef(base=_store(), epoch=1)
    backend = CostModelBackend(costmodel.V5E_ICI)
    co = FleetCoordinator(tmp_path, ref, backend=backend,
                          heartbeat_timeout=100.0, drift_threshold=1.5,
                          clock=lambda: now[0])
    cell = OpCell("allreduce", 4, 512)
    t_model = backend.latency(cell, IMPL)
    # fleet observes the stores' selected impl running 2x the model
    for s in ("s0", "s1"):
        _flush_shard(tmp_path, s, epoch=1,
                     obs=[2.0 * t_model] * 3, seed=hash(s) % 100)
    st = co.scan()
    assert st.drift == pytest.approx(2.0, rel=0.25)
    assert st.retune and any("drift" in r for r in st.reasons)


def test_coordinator_empty_and_quarantined_dirs(tmp_path):
    ref = StoreRef(base=_store(), epoch=0)
    co = FleetCoordinator(tmp_path / "missing", ref, clock=lambda: 0.0)
    st = co.scan()
    assert st.fleet_epoch == -1 and st.drift is None and not st.retune
    # a directory of ONLY corrupt shards: all quarantined, no drift
    p = _flush_shard(tmp_path, "s0", obs=[1e-3] * 3)
    ChaosMonkey(seed=6).tear_shard(p, keep_frac=0.5)
    co2 = FleetCoordinator(tmp_path, ref, clock=lambda: 0.0)
    st2 = co2.scan()
    assert st2.quarantined == 1 and st2.drift is None
