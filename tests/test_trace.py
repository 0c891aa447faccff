"""Workload traces + trace-replay tuning + phase-tagged dispatch.

Covers the ISSUE-2 acceptance path: record a real fwd+bwd LM step,
``tuner.tune_trace`` it into phase-split profiles, and prove ``api``
honors the phase tag at dispatch (bwd reduce-scatters pick a different
mock-up than fwd all-gathers).
"""
import math
import warnings

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import api, tuner
from repro.core.cell import OpCell
from repro.core.profiles import (Profile, ProfileStore, Range, load_stores,
                                 resolve_stores)
from repro.core.trace import Trace, TraceEntry
from repro.dist import ops

P = 4


# ---------------------------------------------------------------------------
# Trace data structure
# ---------------------------------------------------------------------------


def _mk(op="allreduce", p=8, nbytes=1024, phase="fwd", impl="default",
        count=1, **geom):
    return TraceEntry.of(op, p, nbytes, phase, impl, count, **geom)


def test_trace_aggregates_duplicate_cells():
    t = Trace([_mk(count=2), _mk(count=3), _mk(phase="bwd")])
    assert len(t) == 2
    assert t.total() == 6
    assert t.entries[0].count in (1, 5)
    assert {e.phase for e in t} == {"fwd", "bwd"}


def test_trace_jsonl_roundtrip_and_merge():
    t = Trace([_mk(), _mk(op="allgather", phase="decode", nbytes=64,
                          impl="allgather_as_ring", count=7)])
    back = Trace.from_jsonl(t.to_jsonl())
    assert back == t
    m = t.merge(back, back)
    assert m.total() == 3 * t.total()
    assert len(m) == len(t)


def test_trace_save_load(tmp_path):
    t = Trace([_mk(), _mk(phase="bwd", op="reducescatter")])
    t.save(tmp_path / "sub" / "trace.jsonl")
    assert Trace.load(tmp_path / "sub" / "trace.jsonl") == t


def test_trace_histogram_cells_filter():
    t = Trace([_mk(impl="default", count=2),
               _mk(impl="allreduce_as_doubling", count=3),
               _mk(phase="bwd", op="reducescatter", count=5)])
    # histogram keys on the full OpCell and sums over impls (the tuner
    # re-decides the impl)
    ar = OpCell("allreduce", 8, 1024)
    assert t.histogram()[(ar, "fwd")] == 5
    assert t.cells(phase="bwd") == {OpCell("reducescatter", 8, 1024): 5}
    assert t.filter(phase="fwd").ops() == ["allreduce"]
    assert t.phases() == ["bwd", "fwd"]


def test_trace_from_record_matches_api_tuples():
    with api.tuned() as ctx:
        x = jnp.ones((P, 4, 2), jnp.float32)
        with api.phase("decode"):
            jax.vmap(lambda a: api.allreduce(a, "x"), axis_name="x")(x)
    t = Trace.from_context(ctx)
    assert t.cells() == {OpCell("allreduce", P, 32): 1}
    assert t.phases() == ["decode"]


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(min_value=1, max_value=2 ** 30), min_size=1,
                max_size=12),
       st.sampled_from(["fwd", "bwd", "prefill", "decode"]),
       st.sampled_from(["allreduce", "allgather", "scatter"]))
def test_trace_jsonl_roundtrip_property(sizes, phase, op):
    entries = [TraceEntry.of(op, 1 << (i % 10), nb, phase, "default",
                             (i % 5) + 1)
               for i, nb in enumerate(sizes)]
    t = Trace(entries)
    back = Trace.from_jsonl(t.to_jsonl())
    assert back == t
    assert back.total() == t.total()


# ---------------------------------------------------------------------------
# phase tagging at dispatch
# ---------------------------------------------------------------------------


def test_dispatch_records_phase_tags_fwd_and_bwd():
    """dist/ops backward collectives carry phase="bwd" automatically."""
    w = jnp.arange(P * 4 * 2, dtype=jnp.float32).reshape(P, 4, 2)

    def loss(ws):
        full = ops.fsdp_gather(ws, 0, "data")
        return jnp.sum(full * full)

    with api.tuned() as ctx:
        jax.vmap(jax.grad(loss), axis_name="data")(w)
    phases = {(op, ph) for op, _, _, _, ph in ctx.record}
    assert ("allgather", "fwd") in phases
    assert ("reducescatter", "bwd") in phases


def test_phase_profiles_beat_base_profiles_for_matching_phase():
    base = ProfileStore([Profile(op="allreduce", axis_size=P,
                                 ranges=[Range(1, 10 ** 6,
                                               "allreduce_as_reduce_bcast")])])
    pp = {"decode": ProfileStore([
        Profile(op="allreduce", axis_size=P,
                ranges=[Range(1, 10 ** 6, "allreduce_as_doubling")])])}
    x = jnp.ones((P, 4, 2), jnp.float32)
    with api.tuned(profiles=base, phase_profiles=pp) as ctx:
        with api.phase("decode"):
            jax.vmap(lambda a: api.allreduce(a, "x"), axis_name="x")(x)
        jax.vmap(lambda a: api.allreduce(a, "x"), axis_name="x")(x)
    assert (ctx.record[0].impl, ctx.record[0].phase) == \
        ("allreduce_as_doubling", "decode")
    # outside the tagged phase the base store still applies
    assert (ctx.record[1].impl, ctx.record[1].phase) == \
        ("allreduce_as_reduce_bcast", "fwd")


def test_tuned_shared_record_sink():
    sink = []
    x = jnp.ones((P, 2), jnp.float32)
    with api.tuned(record=sink) as ctx:
        jax.vmap(lambda a: api.allreduce(a, "x"), axis_name="x")(x)
    assert ctx.record is sink and len(sink) == 1


def test_env_force_memoized(monkeypatch):
    monkeypatch.setenv("PGTUNE_MODULE", "allreduce:alg=allreduce_as_doubling")
    first = api._env_force()
    assert first == {"allreduce": "allreduce_as_doubling"}
    assert api._env_force() is first            # cache hit, no re-parse
    monkeypatch.setenv("PGTUNE_MODULE", "bcast:alg=bcast_as_tree")
    assert api._env_force() == {"bcast": "bcast_as_tree"}
    monkeypatch.delenv("PGTUNE_MODULE")
    assert api._env_force() == {}


# ---------------------------------------------------------------------------
# trace-replay tuning
# ---------------------------------------------------------------------------


class _StubBackend:
    """Deterministic latencies: ``table[(op, impl)]``, else ``fallback``."""

    name = "stub"

    def __init__(self, table, fallback=10.0):
        self.table = table
        self.fallback = fallback

    def latency(self, cell, impl):
        return self.table.get((cell.op, impl), self.fallback)

    def nrep_for(self, cell, impl):
        return 1


def test_tune_trace_weights_cells_by_frequency():
    t = Trace([_mk(op="allreduce", p=8, nbytes=256, phase="decode",
                   count=100)])
    backend = _StubBackend({("allreduce", "default"): 10.0,
                            ("allreduce", "allreduce_as_doubling"): 1.0})
    rep = tuner.tune_trace(t, backend=backend)
    assert rep.est_default_s["decode"] == pytest.approx(1000.0)
    assert rep.est_tuned_s["decode"] == pytest.approx(100.0)
    prof = rep.phase_profiles["decode"].get("allreduce", 8)
    assert prof.lookup(256) == "allreduce_as_doubling"
    assert prof.meta["phase"] == "decode"


def test_tune_trace_respects_min_win_and_default_inf():
    t = Trace([_mk(op="allreduce", nbytes=64, phase="fwd"),
               _mk(op="allgather", nbytes=64, phase="fwd")])
    backend = _StubBackend({("allreduce", "default"): 10.0,
                            ("allreduce", "allreduce_as_doubling"): 9.5,
                            ("allgather", "default"): math.inf})
    rep = tuner.tune_trace(t, backend=backend, min_win=0.10)
    # 5% win < min_win -> no profile; inf default -> noted skip, no crash
    assert "fwd" not in rep.phase_profiles
    assert any("allgather" in n and "unmeasurable" in n for n in rep.notes)


def test_tune_trace_one_range_per_size_across_dtypes():
    """One size recorded in two dtypes is one profile range: the cells
    decide together, by weighted latency, instead of each emitting a range
    of its own (two impls at one size raised "overlapping ranges")."""
    class ByDtype(_StubBackend):
        def latency(self, cell, impl):
            return {("float32", "allreduce_as_doubling"): 1.0,
                    ("bfloat16", "allreduce_as_rsb_allgather"): 1.0,
                    }.get((cell.dtype, impl), 10.0)

    t = Trace([_mk(nbytes=4096, dtype="float32", count=1),
               _mk(nbytes=4096, dtype="bfloat16", count=3)])
    rep = tuner.tune_trace(t, backend=ByDtype({}), min_win=0.0)
    prof = rep.phase_profiles["fwd"].get("allreduce", 8)
    assert [(r.lo, r.hi, r.impl) for r in prof.ranges] == \
        [(4096, 4096, "allreduce_as_rsb_allgather")]
    assert rep.est_default_s["fwd"] == pytest.approx(40.0)
    assert rep.est_tuned_s["fwd"] == pytest.approx(13.0)


def test_tune_trace_save_roundtrips_through_load_stores(tmp_path):
    t = Trace([_mk(op="allreduce", p=8, nbytes=256, phase="decode"),
               _mk(op="reducescatter", p=8, nbytes=512, phase="bwd")])
    backend = _StubBackend({("allreduce", "default"): 10.0,
                            ("allreduce", "allreduce_as_doubling"): 1.0,
                            ("reducescatter", "default"): 10.0,
                            ("reducescatter", "rsb_as_reduce_scatter"): 1.0})
    rep = tuner.tune_trace(t, backend=backend)
    rep.save(tmp_path)
    base, phases = load_stores(tmp_path)
    assert base is None
    assert set(phases) == {"decode", "bwd"}
    assert phases["decode"].lookup("allreduce", 8, 256) == \
        "allreduce_as_doubling"
    assert phases["bwd"].lookup("reducescatter", 8, 512) == \
        "rsb_as_reduce_scatter"


def test_resolve_stores_precedence(tmp_path, monkeypatch):
    explicit = tmp_path / "explicit"
    env_dir = tmp_path / "env"
    ProfileStore([Profile(op="allreduce", axis_size=4,
                          ranges=[Range(1, 9, "allreduce_as_doubling")])
                  ]).save(explicit)
    ProfileStore([Profile(op="bcast", axis_size=4,
                          ranges=[Range(1, 9, "bcast_as_tree")])
                  ]).save(env_dir)
    monkeypatch.setenv("PGTUNE_PROFILE_DIR", str(env_dir))
    base, _ = resolve_stores(str(explicit))       # arg beats env
    assert base.lookup("allreduce", 4, 5) == "allreduce_as_doubling"
    base_env, _ = resolve_stores(None)            # env fallback
    assert base_env.lookup("bcast", 4, 5) == "bcast_as_tree"
    # stale env var: warn + untuned, never crash a process that didn't
    # ask for profiles; an explicit missing directory still raises
    monkeypatch.setenv("PGTUNE_PROFILE_DIR", str(tmp_path / "missing"))
    with pytest.warns(UserWarning, match="serving untuned"):
        assert resolve_stores(None) == (None, {})
    monkeypatch.delenv("PGTUNE_PROFILE_DIR")
    assert resolve_stores(None) == (None, {})
    with pytest.raises(FileNotFoundError):
        resolve_stores(str(tmp_path / "missing"))


# ---------------------------------------------------------------------------
# acceptance: recorded LM fwd+bwd step -> phase-split profiles -> dispatch
# ---------------------------------------------------------------------------


def _lm_step_ctx(phase_profiles=None):
    """One llama fwd+bwd step under vmap-FSDP, recorded."""
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

    with api.tuned(phase_profiles=phase_profiles) as ctx:
        params = jax.vmap(init, axis_name="data", axis_size=2,
                          in_axes=None, out_axes=0)(jax.random.key(0))
        jax.vmap(grad_fn, axis_name="data")(params)
    return ctx


def test_tune_trace_lm_step_phase_split_end_to_end():
    # 1. record a real fwd+bwd LM step
    ctx = _lm_step_ctx()
    trace = Trace.from_context(ctx)
    assert {"fwd", "bwd"} <= set(trace.phases())
    assert any(c.op == "allgather" for c in trace.cells("fwd"))
    assert any(c.op == "reducescatter" for c in trace.cells("bwd"))

    # 2. tune the recorded mix; stub latencies make the winners
    #    deterministic: fwd allgathers -> ring, bwd reduce-scatters -> the
    #    reduce+scatter mock-up (a DIFFERENT selection per phase)
    backend = _StubBackend({("allgather", "default"): 10.0,
                            ("allgather", "allgather_as_ring"): 1.0,
                            ("reducescatter", "default"): 10.0,
                            ("reducescatter", "rsb_as_reduce_scatter"): 1.0},
                           fallback=50.0)
    rep = tuner.tune_trace(trace, backend=backend)
    fwd, bwd = rep.phase_profiles["fwd"], rep.phase_profiles["bwd"]
    ag_cells = [c for c in trace.cells("fwd") if c.op == "allgather"]
    rs_cells = [c for c in trace.cells("bwd") if c.op == "reducescatter"]
    for c in ag_cells:
        assert fwd.lookup("allgather", c.p, c.nbytes) == "allgather_as_ring"
    for c in rs_cells:
        assert bwd.lookup("reducescatter", c.p, c.nbytes) == \
            "rsb_as_reduce_scatter"

    # 3. re-run the SAME model step under the phase-split stores: api must
    #    honor the phase tag at dispatch
    ctx2 = _lm_step_ctx(phase_profiles=rep.phase_profiles)
    fwd_ag = {impl for op, _, _, impl, ph in ctx2.record
              if op == "allgather" and ph == "fwd"}
    bwd_rs = {impl for op, _, _, impl, ph in ctx2.record
              if op == "reducescatter" and ph == "bwd"}
    assert fwd_ag == {"allgather_as_ring"}
    assert bwd_rs == {"rsb_as_reduce_scatter"}
    assert fwd_ag != bwd_rs


# ---------------------------------------------------------------------------
# serve builders: phase tagging + profile-dir loading on a 1-device mesh
# ---------------------------------------------------------------------------


def test_serve_decode_builder_records_decode_phase(tmp_path, monkeypatch):
    import dataclasses as _dc

    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import build_decode
    from repro.launch.shapes import ShapeCell
    from repro.models import lm as _lm
    from repro.models.params import init_tree

    # a tuned store on disk (wrong axis size on purpose: exercises the
    # loading path without forcing p=1 mock-ups)
    ProfileStore([Profile(op="allreduce", axis_size=16,
                          ranges=[Range(1, 10 ** 6,
                                        "allreduce_as_doubling")])
                  ]).save(tmp_path / "decode")
    monkeypatch.setenv("PGTUNE_PROFILE_DIR", str(tmp_path))

    cfg = get_config("gemma3-1b").smoke()
    cell = ShapeCell("decode_tiny", 32, 2, "decode", n_micro=1)
    mesh = make_host_mesh((1, 1), ("data", "model"))
    record = []
    step, (p_sds, t_sds, c_sds, i_sds) = build_decode(
        cfg, mesh, cell, record=record)

    params = init_tree(_lm.model_specs(cfg, tp=1), jax.random.key(0))
    caches = jax.jit(lambda: _lm.init_caches(cfg, 2, 32))()
    tok = jnp.zeros((2, 1), jnp.int32)
    lg, _ = step(params, tok, caches, jnp.int32(3))
    assert np.asarray(lg).shape[0] == 2
    assert record, "decode step recorded no dispatches"
    assert {ph for *_, ph in record} == {"decode"}


def test_serve_builder_record_only_inherits_ambient_context(monkeypatch):
    """A record-only builder must not shadow a caller-managed api.tuned:
    its inner context inherits the ambient profiles/force."""
    monkeypatch.delenv("PGTUNE_PROFILE_DIR", raising=False)
    from repro.launch.serve import _serving_ctx

    x = jnp.ones((P, 4, 2), jnp.float32)
    sink = []

    def step(a):
        with _serving_ctx("decode", None, None, None, sink):
            return api.allreduce(a, "x")

    with api.tuned(force={"allreduce": "allreduce_as_doubling"}) as outer:
        jax.vmap(step, axis_name="x")(x)
    assert [tuple(r) for r in sink] == \
        [("allreduce", P, 32, "allreduce_as_doubling", "decode")]
    assert outer.record == []          # sink swapped, tuning inherited


# ---------------------------------------------------------------------------
# v1 sunset step: deprecation warnings + mixed-schema shard merging
# ---------------------------------------------------------------------------

V1_LINE = ('{"op": "allreduce", "p": 4, "nbytes": 512, "phase": "bwd", '
           '"impl": "default", "count": 3}\n')


def test_v1_trace_load_warns_naming_the_file(tmp_path):
    """Satellite: loading a v1 trace file now emits a DeprecationWarning
    that names the offending file (the sunset breadcrumb)."""
    f = tmp_path / "old_shard.jsonl"
    f.write_text(V1_LINE)
    with pytest.warns(DeprecationWarning, match="old_shard.jsonl"):
        t = Trace.load(f)
    assert t.total() == 3


def test_v2_trace_load_does_not_warn(tmp_path):
    f = tmp_path / "new_shard.jsonl"
    Trace([TraceEntry.of("allreduce", 4, 512, "bwd")]).save(f)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        Trace.load(f)


def test_v1_line_with_v_in_string_value_still_warns():
    """Satellite regression (false negative): a v1 line whose IMPL string
    happens to be the single character "v" satisfied the old substring
    test ('\"v\"' in line) and was silently treated as v2.  Detection must
    key on the decoded object's keys, not the raw text."""
    sneaky_v1 = ('{"op": "allreduce", "p": 4, "nbytes": 512, '
                 '"phase": "bwd", "impl": "v", "count": 3}\n')
    with pytest.warns(DeprecationWarning, match="schema-v1"):
        t = Trace.from_jsonl(sneaky_v1)
    assert t.total() == 3
    assert t.entries[0].impl == "v"


def test_v2_line_with_v_valued_strings_parses_cleanly():
    """The complementary shape: a REAL v2 line carrying "v" inside string
    values must parse without any deprecation path firing and keep its
    recorded geometry."""
    e = TraceEntry.of("allgather_matmul", 4, 2048, "fwd", impl="v",
                      count=2, mm_k=64, mm_m=128, mm_n=32,
                      mm_role="gather")
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        back = Trace.from_jsonl(e.to_json() + "\n")
    assert back.entries[0] == e
    assert back.entries[0].cell.mm_k == 64


def test_v1_profile_file_load_warns_naming_schema(tmp_path):
    """A .pgtune file without the 'pgtune profile v2' header is schema v1:
    ProfileStore.load warns (and still serves it)."""
    from repro.core.profiles import Profile, ProfileStore, Range
    d = tmp_path
    (d / "allreduce_p4.pgtune").write_text(
        "# pgtune profile\nMPI_Allreduce\n4 # nb. of. processes\n"
        "1 # nb. of mock-up impl.\n2 allreduce_as_doubling\n"
        "1 # nb. of ranges\n1 4096 2\n")
    with pytest.warns(DeprecationWarning, match="allreduce_p4.pgtune"):
        store = ProfileStore.load(d)
    assert store.lookup("allreduce", 4, 64) == "allreduce_as_doubling"
    # files re-saved by the current code carry the v2 header: no warning
    store2 = ProfileStore([Profile(op="allreduce", axis_size=4,
                                   ranges=[Range(1, 9, "allreduce_as_doubling")])])
    d2 = tmp_path / "v2"
    store2.save(d2, fmt="text")
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        ProfileStore.load(d2)


def test_merge_mixed_v1_v2_server_shards_roundtrip(tmp_path):
    """Satellite: cross-server shard merging with MIXED schemas — one v1
    shard (defaulted geometry), one v2 shard (full 1-D + 2-D geometry
    cells) — must aggregate cell-wise, and the merged trace must be stable
    under a v2 save/load round-trip (the migration path)."""
    v1 = tmp_path / "server_a.jsonl"
    v1.write_text(V1_LINE + '{"op": "allgather_matmul", "p": 4, '
                            '"nbytes": 2048, "phase": "fwd", '
                            '"impl": "default", "count": 2}\n')
    v2 = tmp_path / "server_b.jsonl"
    Trace([
        TraceEntry.of("allreduce", 4, 512, "bwd", count=5),
        TraceEntry.of("allgather_matmul", 4, 2048, "fwd", count=1,
                      mm_k=64, mm_m=128, mm_n=32, mm_role="gather"),
        TraceEntry.of("matmul_reducescatter_2d", 2, 4096, "fwd", count=4,
                      mm_k=64, mm_m=128, mm_n=32, mm_role="2d", p2=2),
    ]).save(v2)
    with pytest.warns(DeprecationWarning, match="server_a.jsonl"):
        ta = Trace.load(v1)
    tb = Trace.load(v2)
    merged = ta.merge(tb)
    # the v1 allreduce cell and the v2 one share geometry -> one cell
    assert merged.cells()[OpCell("allreduce", 4, 512)] == 8
    # the v1 geometry-less agmm cell stays DISTINCT from the v2 geometry
    # cell (different communication problems)
    agmm = [c for c in merged.cells() if c.op == "allgather_matmul"]
    assert len(agmm) == 2
    cell2d = [c for c in merged.cells()
              if c.op == "matmul_reducescatter_2d"][0]
    assert cell2d.p2 == 2 and cell2d.world() == 4
    out = tmp_path / "merged.jsonl"
    merged.save(out)
    assert '"v": 2' in out.read_text()
    assert Trace.load(out) == merged        # v2 round-trip is identity


def test_trace_2d_cell_jsonl_carries_p2():
    e = TraceEntry.of("matmul_reducescatter_2d", 4, 1 << 20, "bwd",
                      "fused_ring2d", 2, mm_k=256, mm_m=512, mm_n=128,
                      mm_role="2dT", p2=8)
    line = e.to_json()
    assert '"p2": 8' in line and '"role": "2dT"' in line
    back = TraceEntry.from_json(line)
    assert back == e and back.cell.world() == 32
