"""Shape-aware tuning cells: OpCell, trace schema v2 (+v1 back-compat),
geometry-keyed profiles with nearest-cell fallback, and the measured
backend replaying the RECORDED GEMM (the MM_WIDTH regression)."""
import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import api, costmodel as cm, measure, tuner
from repro.core.cell import Geom, OpCell
from repro.core.profiles import (Profile, ProfileStore, Range,
                                 resolve_stores)
from repro.core.trace import Trace, TraceEntry


# ---------------------------------------------------------------------------
# OpCell
# ---------------------------------------------------------------------------


def test_opcell_plain_vs_fused():
    plain = OpCell("allreduce", 8, 1024)
    assert not plain.fused and plain.geom() is None
    fused = OpCell("allgather_matmul", 8, 4096, "bfloat16",
                   mm_k=256, mm_m=128, mm_n=64, mm_role="gather")
    assert fused.fused
    assert fused.geom() == Geom("bfloat16", 256, 128, 64, "gather")
    assert fused.itemsize == 2
    assert fused.flops() == 2 * 256 * 128 * 64
    with pytest.raises(ValueError):
        OpCell("allgather_matmul", 8, 4, mm_k=2, mm_m=2, mm_n=2,
               mm_role="bogus")


def test_opcell_scaled_to_keeps_geometry_consistent():
    c = OpCell("allgather_matmul", 4, 4096, "float32",
               mm_k=64, mm_m=64, mm_n=32, mm_role="gather")
    s = c.scaled_to(4096 * 16)
    assert s.mm_k == 64 and s.mm_n == 32          # aspect preserved
    assert s.nbytes == (s.mm_m // 4) * 64 * 4     # payload consistent
    acc = OpCell("matmul_accumulate", 4, 1024, "float32",
                 mm_k=16, mm_m=8, mm_n=64, mm_role="contract")
    s2 = acc.scaled_to(1024 * 8)
    assert s2.mm_n == 64 and s2.mm_m == 8
    assert s2.nbytes == (s2.mm_k // 4) * 64 * 4


# ---------------------------------------------------------------------------
# trace schema v2 + v1 back-compat
# ---------------------------------------------------------------------------


def test_trace_v2_roundtrips_geometry():
    e = TraceEntry.of("allgather_matmul", 8, 4096, "bwd", "fused_ring", 3,
                      dtype="bfloat16", mm_k=512, mm_m=1024, mm_n=64,
                      mm_role="gather")
    t = Trace([e])
    back = Trace.from_jsonl(t.to_jsonl())
    assert back == t
    cell = next(iter(back.cells()))
    assert (cell.dtype, cell.mm_k, cell.mm_m, cell.mm_n, cell.mm_role) == \
        ("bfloat16", 512, 1024, 64, "gather")
    assert '"v": 2' in e.to_json()


def test_trace_v1_lines_load_with_defaulted_geometry():
    """Satellite: old 5-field JSONL lines still parse — geometry defaulted,
    fused ops marked unknown (fused=False)."""
    v1 = ('{"op": "reducescatter", "p": 8, "nbytes": 4096, "phase": "bwd", '
          '"impl": "default", "count": 24}\n'
          '{"op": "allgather_matmul", "p": 4, "nbytes": 2048, '
          '"phase": "fwd", "impl": "fused_ring", "count": 2}\n')
    t = Trace.from_jsonl(v1)
    assert t.total() == 26
    ag, rs = sorted(t.cells(), key=lambda c: c.op)
    assert ag.op == "allgather_matmul" and not ag.fused
    assert rs.op == "reducescatter" and rs.dtype == "float32"


def test_trace_v1_to_v2_migration_roundtrip(tmp_path):
    """v1 file -> load -> save (v2) -> load: identical cells, and the v2
    form is stable under a further round-trip."""
    v1_path = tmp_path / "old.jsonl"
    v1_path.write_text(
        '{"op": "allreduce", "p": 16, "nbytes": 512, "phase": "decode", '
        '"impl": "allreduce_as_doubling", "count": 7}\n')
    t1 = Trace.load(v1_path)
    v2_path = tmp_path / "new.jsonl"
    t1.save(v2_path)
    assert '"v": 2' in v2_path.read_text()
    t2 = Trace.load(v2_path)
    assert t2 == t1
    assert Trace.from_jsonl(t2.to_jsonl()) == t2


def test_from_record_accepts_legacy_tuples():
    t = Trace.from_record([("allreduce", 4, 128, "default", "fwd")])
    assert t.cells() == {OpCell("allreduce", 4, 128): 1}


# ---------------------------------------------------------------------------
# geometry-keyed profiles + nearest-cell fallback
# ---------------------------------------------------------------------------

G = Geom("float32", 512, 1024, 256, "gather")


def _geom_profile(geom=G, impl="fused_ring", lo=1, hi=10**7):
    return Profile(op="allgather_matmul", axis_size=8,
                   ranges=[Range(lo, hi, impl)], geom=geom)


def test_profile_geom_text_and_json_roundtrip():
    prof = _geom_profile()
    t = Profile.from_text(prof.to_text())
    assert t.geom == G and t.ranges == prof.ranges
    j = Profile.from_json(prof.to_json())
    assert j.geom == G and j.ranges == prof.ranges


def test_v1_profile_text_still_loads_geomless():
    """Satellite: a v1 .pgtune file (no #@geom line) loads with geom=None
    and keeps serving geometry-less lookups."""
    prof = Profile(op="allgather", axis_size=8,
                   ranges=[Range(1, 100, "allgather_as_ring")])
    text = prof.to_text()
    assert "#@geom" not in text
    back = Profile.from_text(text)
    assert back.geom is None
    store = ProfileStore([back])
    assert store.lookup("allgather", 8, 50) == "allgather_as_ring"


def test_resolve_stores_loads_v1_profile_files(tmp_path, monkeypatch):
    d = tmp_path / "profiles"
    d.mkdir()
    # a hand-written v1 Listing-1 file, no geometry anywhere
    (d / "allreduce_p4.pgtune").write_text(
        "# pgtune profile\nMPI_Allreduce\n4 # nb. of. processes\n"
        "1 # nb. of mock-up impl.\n2 allreduce_as_doubling\n"
        "1 # nb. of ranges\n1 4096 2\n")
    monkeypatch.delenv("PGTUNE_PROFILE_DIR", raising=False)
    base, phases = resolve_stores(str(d))
    assert phases == {}
    assert base.lookup("allreduce", 4, 64) == "allreduce_as_doubling"


def test_store_lookup_cell_exact_nearest_and_fallback():
    near = Geom("float32", 512, 2048, 256, "gather")       # 2x rows off
    far = Geom("float32", 64, 64, 64, "gather")
    other_role = Geom("float32", 512, 1024, 256, "scatter")
    store = ProfileStore([
        _geom_profile(G, "fused_ring"),
        _geom_profile(far, "default", lo=1, hi=10),
        Profile(op="matmul_reducescatter", axis_size=8,
                ranges=[Range(1, 10**7, "fused_ring")], geom=other_role),
        Profile(op="allgather_matmul", axis_size=8,
                ranges=[Range(1, 10**7, "default")]),      # geom-less base
    ])
    exact = OpCell("allgather_matmul", 8, 4096, "float32",
                   512, 1024, 256, "gather")
    assert store.lookup_cell(exact) == "fused_ring"
    # unseen shape: resolves to the NEAREST tuned geometry (near > far)
    store.add(_geom_profile(near, "fused_ring"))
    unseen = OpCell("allgather_matmul", 8, 4096, "float32",
                    512, 4096, 256, "gather")
    assert store.lookup_cell(unseen) == "fused_ring"
    # nbytes outside the nearest profile's ranges: lookup_nearest covers it
    unseen_big = OpCell("allgather_matmul", 8, 10**9, "float32",
                        512, 10**6, 256, "gather")
    assert store.lookup_cell(unseen_big) == "fused_ring"
    # plain cells never consult geometry profiles
    plain = OpCell("allgather_matmul", 8, 4096)
    assert store.lookup_cell(plain) == "default"


def test_store_save_load_geometry_files(tmp_path):
    store = ProfileStore([_geom_profile(),
                          Profile(op="allreduce", axis_size=8,
                                  ranges=[Range(1, 9, "allreduce_as_doubling")])])
    store.save(tmp_path, fmt="text")
    names = sorted(p.name for p in tmp_path.glob("*.pgtune"))
    assert any("k512m1024n256" in n for n in names), names
    back = ProfileStore.load(tmp_path)
    assert len(back) == 2
    cell = OpCell("allgather_matmul", 8, 4096, "float32",
                  512, 1024, 256, "gather")
    assert back.lookup_cell(cell) == "fused_ring"
    assert back.lookup("allreduce", 8, 5) == "allreduce_as_doubling"


def test_dispatch_uses_geometry_profile_for_exact_cell(rng):
    """api.tuned(profiles=...) routes a fused dispatch through its geometry
    profile; a different-geometry callsite falls back per nearest/geomless
    rules."""
    p, n, k, m = 4, 4, 8, 6
    geom = Geom("float32", k, p * n, m, "gather")
    store = ProfileStore([Profile(op="allgather_matmul", axis_size=p,
                                  ranges=[Range(1, 10**6, "fused_ring")],
                                  geom=geom)])
    x = jnp.asarray(rng.normal(size=(p, n, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(k, m)).astype(np.float32))
    with api.tuned(profiles=store) as ctx:
        jax.vmap(lambda a: api.allgather_matmul(a, w, "x"),
                 axis_name="x")(x)
    assert [r.impl for r in ctx.record] == ["fused_ring"]
    assert ctx.record[0].cell.geom() == geom


# ---------------------------------------------------------------------------
# measured backend replays the RECORDED GEMM (MM_WIDTH regression)
# ---------------------------------------------------------------------------


def test_problem_shapes_use_recorded_gemm_not_square():
    """Regression: replay of an allgather_matmul cell must build the
    recorded (mm_k, mm_m, mm_n) problem — not a 64-wide square weight."""
    cell = OpCell("allgather_matmul", 1, 48 * 10 * 4, "float32",
                  mm_k=48, mm_m=10, mm_n=24, mm_role="gather")
    shapes = measure.problem_shapes(cell)
    assert shapes == {"x": (10, 48), "w": (48, 24)}
    mmrs = OpCell("matmul_reducescatter", 2, 0, "float32",
                  mm_k=16, mm_m=6, mm_n=10, mm_role="scatter")
    assert measure.problem_shapes(mmrs) == {"x": (6, 16), "w": (16, 10)}
    acc = OpCell("matmul_accumulate", 2, 0, "float32",
                 mm_k=12, mm_m=7, mm_n=5, mm_role="contract")
    assert measure.problem_shapes(acc) == {"x": (6, 5), "w": (7, 12)}


def test_problem_shapes_reject_unknown_geometry():
    with pytest.raises(ValueError, match="no recorded matmul geometry"):
        measure.problem_shapes(OpCell("allgather_matmul", 1, 4096))


def test_measured_replay_of_recorded_agmm_cell():
    """End-to-end on the host device(s): a recorded allgather_matmul cell
    with a non-square GEMM is wall-clock replayed; a v1-style cell without
    geometry is note-skipped instead of silently replaying a canonical
    weight."""
    p = measure.axis_size()
    cell = measure.host_cell("allgather_matmul", 5 * 48 * 4,
                             mm_k=48, mm_m=p * 5, mm_n=12, mm_role="gather")
    lats = measure.sample_latency(cell, "default", 2)
    assert len(lats) == 2 and all(t >= 0.0 for t in lats)

    backend = tuner.MeasuredBackend(K=2, max_nrep=3)
    assert math.isinf(backend.latency(
        measure.host_cell("allgather_matmul", 4096), "default"))
    t = Trace([TraceEntry(measure.host_cell("allgather_matmul", 4096),
                          "fwd", "default", 2)])
    rep = tuner.tune_trace(t, backend=backend)
    assert any("unmeasurable" in n for n in rep.notes)
    assert rep.measurements == []


def test_tune_sweep_emits_geomless_profiles_for_fused_ops():
    """The sweep tuner (synthetic sizes, canonical pricing) and the trace
    tuner (recorded geometry) share _measure_cell; sweep profiles stay
    geometry-less so both lookup paths coexist in one store."""
    rep = tuner.tune(ops=["allgather_matmul"], sizes=(16_777_216,),
                     axis_size=8,
                     backend=tuner.CostModelBackend(cm.V5E_ICI))
    prof = rep.profiles.get("allgather_matmul", 8)
    assert prof is not None and prof.geom is None


def test_tune_trace_geometryless_fused_cell_note_in_footer():
    """Regression (v1-trace inf skip): a fused cell with no recorded GEMM
    used to vanish into a generic 'default impl unmeasurable' note; the
    report must now say WHY (no geometry — re-record) and the note must
    surface in the tuner report's summary footer."""
    t = Trace([TraceEntry(OpCell("allgather_matmul", measure.axis_size(),
                                 4096), "decode", "default", 4)])
    backend = tuner.MeasuredBackend(K=2, max_nrep=3)
    rep = tuner.tune_trace(t, backend=backend)
    geom_notes = [n for n in rep.notes if "no recorded GEMM geometry" in n]
    assert geom_notes, rep.notes
    assert "re-record" in geom_notes[0]
    assert "v1 trace" in geom_notes[0]
    # the note reaches the human-facing report footer
    assert "no recorded GEMM geometry" in rep.summary()
    # and the cell contributed nothing silently: no measurement, no est
    assert rep.measurements == []
    assert rep.est_default_s.get("decode", 0.0) == 0.0


def test_opcell_2d_scaled_to_keeps_geometry_consistent():
    """NREP probes of 2-D cells rescale the payload-tied dim: the forward
    scales the streamed weight's width (mm_n), the transpose the streamed
    cotangent's rows (mm_k)."""
    fwd = OpCell("matmul_reducescatter_2d", 4, 64 * 8 * 4, "float32",
                 mm_k=64, mm_m=32, mm_n=4 * 8, mm_role="2d", p2=2)
    s = fwd.scaled_to(64 * 8 * 4 * 16)
    assert s.mm_k == 64 and s.mm_m == 32 and s.p2 == 2
    assert s.nbytes == (s.mm_n // 4) * 64 * 4
    xp = OpCell("matmul_reducescatter_2d", 2, 6 * 32 * 4, "float32",
                mm_k=2 * 6, mm_m=32, mm_n=16, mm_role="2dT", p2=4)
    s2 = xp.scaled_to(6 * 32 * 4 * 8)
    assert s2.mm_m == 32 and s2.mm_n == 16 and s2.p2 == 4
    assert s2.nbytes == (s2.mm_k // 2) * 32 * 4
    # minimal floor: one row/col block, never a literal byte
    assert fwd.scaled_to(1).mm_n == 4
    assert xp.scaled_to(1).mm_k == 2


def test_problem_shapes_2d_cells():
    """2-D replay shapes: the payload keeps its per-shard form (weight col
    block / cotangent row block), the stationary operand the recorded
    per-rank shape, rows padded to divide the inner axis."""
    fwd = OpCell("matmul_reducescatter_2d", 2, 0, "float32",
                 mm_k=8, mm_m=6, mm_n=2 * 5, mm_role="2d", p2=2)
    assert measure.problem_shapes(fwd) == {"x": (8, 5), "w": (6, 8)}
    xp = OpCell("matmul_reducescatter_2d", 2, 0, "float32",
                mm_k=2 * 3, mm_m=8, mm_n=4, mm_role="2dT", p2=2)
    assert measure.problem_shapes(xp) == {"x": (3, 8), "w": (6, 4)}
    with pytest.raises(ValueError, match="no recorded matmul geometry"):
        measure.problem_shapes(
            OpCell("matmul_reducescatter_2d", 2, 64))
