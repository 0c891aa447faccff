"""XLA-layer interposition: HLO site -> OpCell mapping, the tuning-
potential report, and rewrite-mode bit-exactness (subprocess SPMD).

Synthetic fixtures pin the mapping rules (fused-matmul adjacency roles);
subprocess tests drive the real pipeline: a scanned decode-like jitted
module (trip-count multipliers on real XLA output), the two-model zoo scan
(zero unmapped collectives — the acceptance gate), and a >=4-device
rewrite with movement mock-ups substituted, asserted bit-exact.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.analysis.interpose import (PotentialReport, _match_records_to_sites,
                                      map_sites, scan_potential)
from repro.core.api import DispatchRecord
from repro.core.cell import OpCell

ROOT = pathlib.Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")


def _run(code, *args, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", code, *args],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)


# ---------------------------------------------------------------------------
# mapping rules (synthetic fixture)
# ---------------------------------------------------------------------------

FUSED_FIXTURE = """
HloModule t_fused, num_partitions=4

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

ENTRY %main (x: f32[8,16], w: f32[16,32], xk: f32[8,16], w2: f32[32,24]) -> f32[8,32] {
  %x = f32[8,16]{1,0} parameter(0)
  %w = f32[16,32]{1,0} parameter(1)
  %xk = f32[8,16]{1,0} parameter(2)
  %w2 = f32[32,24]{1,0} parameter(3)
  %ag = f32[32,16]{1,0} all-gather(%x), replica_groups={{0,1,2,3}}, dimensions={0}
  %dot = f32[32,32]{1,0} dot(%ag, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %agk = f32[32,16]{1,0} all-gather(%xk), replica_groups={{0,1,2,3}}, dimensions={0}
  %dotk = f32[16,16]{1,0} dot(%agk, %agk), lhs_contracting_dims={0}, rhs_contracting_dims={0}
  %dot2 = f32[32,24]{1,0} dot(%dot, %w2), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[32,24]{1,0} all-reduce(%dot2), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %rs = f32[8,32]{1,0} reduce-scatter(%dot), replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add
}
"""


def test_map_sites_fused_roles():
    mapped, unmapped = map_sites(FUSED_FIXTURE)
    assert unmapped == []
    by_name = {sc.site.name: sc for sc in mapped}

    # all-gather on the dot's ROW dim (not contracted) -> allgather_matmul
    ag = by_name["ag"]
    assert ag.fused and ag.adjacent_dot == "dot"
    assert ag.cell.op == "allgather_matmul"
    assert ag.cell.mm_role == "gather"
    assert (ag.cell.mm_k, ag.cell.mm_m, ag.cell.mm_n) == (16, 32, 32)
    assert ag.cell.nbytes == 8 * 16 * 4          # the pre-gather shard
    assert ag.cell.p == 4

    # all-gather whose gathered dim IS contracted -> matmul_accumulate
    agk = by_name["agk"]
    assert agk.cell.op == "matmul_accumulate"
    assert agk.cell.mm_role == "contract"
    assert agk.cell.mm_k == 32

    # dot -> reduce-scatter -> matmul_reducescatter; payload = dot's lhs
    rs = by_name["rs"]
    assert rs.cell.op == "matmul_reducescatter"
    assert rs.cell.mm_role == "scatter"
    assert rs.cell.nbytes == 32 * 16 * 4
    assert (rs.cell.mm_k, rs.cell.mm_m, rs.cell.mm_n) == (16, 32, 32)

    # dot -> all-reduce: stays a plain cell, flagged as a fused candidate
    ar = by_name["ar"]
    assert not ar.fused and ar.adjacent_dot == "dot2"
    assert ar.cell.op == "allreduce"
    assert ar.cell.nbytes == 32 * 24 * 4


def test_scan_potential_report():
    rep = scan_potential(FUSED_FIXTURE, label="fixture")
    assert isinstance(rep, PotentialReport)
    assert rep.ok and len(rep.rows) == 4
    assert rep.world == 4
    assert rep.potential() >= 1.0
    assert rep.total_default() >= rep.total_best() > 0
    table = rep.table()
    assert "collectives vs. best mock-ups:" in table
    assert "x on the table" in table
    j = rep.to_json()
    assert j["ok"] and j["n_sites"] == 4 and j["n_unmapped"] == 0
    json.dumps(j)        # artifact-serializable


def test_match_records_to_sites():
    sites = [sc.site for sc in map_sites(FUSED_FIXTURE)[0]]
    recs = [
        DispatchRecord(OpCell.plain("allgather", 4, 8 * 16 * 4), "default",
                       ""),
        DispatchRecord(OpCell.plain("allreduce", 4, 32 * 24 * 4),
                       "default", ""),
        DispatchRecord(OpCell.plain("allreduce", 4, 999), "default", ""),
        DispatchRecord(OpCell.plain("allgather", 1, 64), "default", ""),
    ]
    matched, unmatched, free = _match_records_to_sites(recs, sites)
    assert [(r.op, s.name) for r, s in matched] == [
        ("allgather", "ag"), ("allreduce", "ar")]
    assert [r.nbytes for r in unmatched] == [999]   # no such site
    assert {s.name for s in free} == {"agk", "rs"}  # p=1 rec never matches


# ---------------------------------------------------------------------------
# real scanned module: trip-count multipliers on compiled XLA output
# ---------------------------------------------------------------------------

SCAN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_host_mesh
from repro.analysis.hlo import _loop_multipliers, collective_bytes, collective_sites

mesh = make_host_mesh((4,), ("model",))
STEPS = 6

def body(x):
    # decode-like loop: per-step partial matmul + psum over the TP axis
    def step(carry, _):
        y = carry @ carry.T @ carry
        return lax.psum(y, "model"), ()
    out, _ = lax.scan(step, x, None, length=STEPS)
    return out

fn = shard_map(body, mesh=mesh, in_specs=(P(None, "model"),),
               out_specs=P(None, "model"), check_vma=False)
x = jnp.ones((8, 16), jnp.float32)
with mesh:
    hlo = jax.jit(fn).lower(x).compile().as_text()
mults = _loop_multipliers(hlo)
cb = collective_bytes(hlo)
sites = collective_sites(hlo)
print(json.dumps({
    "mults": sorted(mults.values()),
    "ar": cb.get("all-reduce", {}),
    "site_mults": [s.mult for s in sites if s.base_op == "all-reduce"],
}))
"""


@pytest.mark.slow
def test_loop_multiplier_real_scanned_module():
    """A jitted scan (decode-step shape) compiles to a while loop; the
    collectives inside must be weighted by the recovered trip count."""
    r = _run(SCAN_SCRIPT)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert 6 in out["mults"], out
    assert out["site_mults"] and all(m == 6 for m in out["site_mults"]), out
    # one psum per iteration: bytes scale with the trip count
    assert out["ar"]["count"] == 6, out
    assert out["ar"]["bytes"] == 6 * 8 * 4 * 4, out


# ---------------------------------------------------------------------------
# zoo scan: every collective of two real models maps (the acceptance gate)
# ---------------------------------------------------------------------------

ZOO_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
from repro.analysis.hlo import parse_instructions
from repro.analysis.interpose import compile_zoo_hlo, scan_potential

out = {}
for arch in ("gemma3-1b", "llama3.2-3b"):
    hlo, _info = compile_zoo_hlo(arch, kind="decode", mesh_shape=(2, 4))
    rep = scan_potential(hlo, label=arch)
    instrs = parse_instructions(hlo)
    out[arch] = {
        "ok": rep.ok,
        "n_sites": len(rep.rows),
        "unmapped": [s.hlo_op for s in rep.unmapped],
        "potential": rep.potential(),
        "n_instrs": len(instrs),
        "n_scalar": sum(1 for i in instrs if i.type_str.endswith("[]")),
        "n_tuple": sum(1 for i in instrs
                       if i.type_str.startswith("(")),
        "table_ok": "x on the table" in rep.table(),
    }
print(json.dumps(out))
"""


@pytest.mark.slow
def test_zoo_scan_zero_unmapped_two_models():
    """Report-only mode must map EVERY collective instruction of >=2 zoo
    models to a priced OpCell — zero unmapped ops (and the real compiled
    modules double as parser fixtures: scalar + tuple result types)."""
    r = _run(ZOO_SCRIPT)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(out) == {"gemma3-1b", "llama3.2-3b"}
    for arch, d in out.items():
        assert d["ok"] and d["unmapped"] == [], (arch, d)
        assert d["n_sites"] > 0, (arch, d)
        assert d["potential"] >= 1.0, (arch, d)
        assert d["table_ok"], (arch, d)
        # hardening coverage on real compiled text: scalar results
        # (f32[]/s32[] loop counters) and tuple-typed instructions parse
        assert d["n_scalar"] > 0, (arch, d)
        assert d["n_tuple"] > 0, (arch, d)


# ---------------------------------------------------------------------------
# rewrite mode: >=4-device SPMD bit-exactness
# ---------------------------------------------------------------------------

REWRITE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_host_mesh
from repro.core import api
from repro.analysis.interpose import assert_bitexact, rewrite

mesh = make_host_mesh((4,), ("model",))

def body(x, w):
    g = api.allgather(x, "model")
    y = g @ w
    s = api.reducescatter(y, "model")
    z = api.allreduce(s * 2.0, "model")
    return api.alltoall(z, "model")

fn = shard_map(body, mesh=mesh, in_specs=(P("model"), P()),
               out_specs=P("model"), check_vma=False)
x = jnp.arange(16 * 16, dtype=jnp.float32).reshape(16, 16) / 7.0
w = jnp.ones((16, 16), jnp.float32) * 0.5

with mesh:
    # movement mock-ups only: reduction mock-ups reorder the sum and are
    # legitimately not bit-exact
    res = rewrite(fn, x, w,
                  force={"allgather": "allgather_as_ring",
                         "alltoall": "alltoall_as_ppermute"})
assert_bitexact(res)
print(json.dumps({
    "matched": [(r.op, s.name) for r, s in res.matched],
    "unmatched": [r.op for r in res.unmatched_records],
    "extra": [s.name for s in res.extra_sites],
    "changed": sorted((r.op, r.impl) for r in res.changed),
    "bitexact": res.bitexact,
}))
"""


@pytest.mark.slow
def test_rewrite_bitexact_4dev_spmd():
    """Rewrite mode substitutes tuned mock-ups at matched dist-shaped
    sites and the program output stays bit-for-bit identical."""
    r = _run(REWRITE_SCRIPT)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["bitexact"] is True
    assert out["changed"] == [["allgather", "allgather_as_ring"],
                              ["alltoall", "alltoall_as_ppermute"]]
    # every dispatch matched an HLO site, and vice versa
    assert out["unmatched"] == [] and out["extra"] == []
    assert {op for op, _ in out["matched"]} == {
        "allgather", "reducescatter", "allreduce", "alltoall"}
