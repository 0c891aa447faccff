"""Hierarchical per-axis topology: fitted tiers, (2,2,2) trace, must-wins.

The flat-link cost model priced every mesh axis with one ``Topo`` even
when a cell's ring crossed the ~4x ICI/DCN bandwidth gap.  This benchmark
exercises the per-axis replacement end to end and gates the wins:

1. FIT — a subprocess forces 8 host devices and runs
   ``measure.sweep_axis`` allgather + allreduce sweeps; ``fit_topo``
   turns them into the reachable tier's alpha/beta/gamma.  The DCN tier
   is DERIVED from those fitted absolutes with the published relative
   gaps (``Topo.scaled`` x ``DCN_ALPHA_MULT``/``DCN_BW_MULT``) — no
   hard-coded constants enter the mesh the tuner prices with.
2. TRACE — a (pod, data, model) = (2, 2, 2) shard_map harness runs
   DCN-crossing hierarchical collectives (``inner_axis=`` dispatch) and
   a flat intra-pod sync under ``api.tuned(record=..., mesh_topo=...)``;
   the recorded cells carry ``p2`` and the tier token.
3. TUNE + MUST-WIN — ``tune_trace`` over the fitted ``MeshTopo`` must
   select the hierarchical mock-ups on the DCN-crossing cells; the
   modeled allreduce win over the flat joint ring (the cell the ISSUE
   names) must clear ``RATIO_GATE``; the flat sibling must never pick a
   hierarchical impl.
4. LAYOUT — the mesh-layout question: re-key the traced grad-sync cell
   to the candidate layouts of the same world (flat ring on DCN,
   DCN-outer hierarchy, DCN-inner hierarchy) and compare each layout's
   best LOSSLESS schedule; the DCN-outer hierarchy must win outright.

Payload size is chosen FROM THE FIT (smallest power of two making the
modeled bandwidth term dominate the DCN alpha term), so the must-win
cells are comm-bound by construction on whatever this host measures.

  PYTHONPATH=src python benchmarks/bench_hierarchy.py --smoke
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import subprocess
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT))
sys.path.insert(0, str(_ROOT / "src"))

from benchmarks.common import emit, header
from repro.core import costmodel as cm, tuner
from repro.core.cell import OpCell
from repro.core.collectives import REGISTRY
from repro.core.trace import Trace, TraceEntry

RATIO_GATE = 1.1        # modeled default/hier floor on the must-win cell
HIER_IMPL = {"allreduce": "MPIX_rs_ar_ag", "allgather": "MPIX_ag_ag",
             "reducescatter": "MPIX_rs_rs"}

FIT_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
from repro.core import measure
sizes = [int(s) for s in sys.argv[1].split(",")]
count = int(sys.argv[2])
print(json.dumps({
    "p": measure.axis_size(),
    "allgather": measure.sweep_axis("allgather", sizes, count=count),
    "allreduce": measure.sweep_axis("allreduce", sizes, count=count),
}))
"""

TRACE_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from repro.core import api, costmodel as cm
from repro.core.trace import Trace, TraceEntry
from repro.launch.mesh import make_host_mesh

spec = json.loads(sys.argv[1])
mk = lambda d: cm.Topo(d["name"], alpha=d["alpha"], link_bw=d["link_bw"],
                       gamma=d["gamma"])
ici, dcn = mk(spec["ici"]), mk(spec["dcn"])
mt = cm.MeshTopo.of(pod=dcn, data=ici, model=ici)
mesh = make_host_mesh((2, 2, 2), ("pod", "data", "model"))

n = max(spec["nbytes"] // 4, 8)          # float32 elements per rank
r_rows = 4 * max(n // 8, 1)              # reducescatter: divisible by world

def body(xa, xr, xs):
    # DCN-crossing hierarchical group: pod (inter) outer, data (intra) in
    g = api.allreduce(xa[0], "pod", inner_axis="data")
    h = api.allgather(xa[0], "pod", inner_axis="data")
    r = api.reducescatter(xr[0], "pod", inner_axis="data")
    # flat intra-pod sync: the sibling that must NOT pick a hier mock-up
    s = api.allreduce(xs[0], "model")
    # all-ones input: allreduce/reducescatter sum 4 ranks, gather keeps 1,
    # model allreduce sums 2 — max deviation is the semantic check
    return (jnp.abs(g - 4.0).max() + jnp.abs(h - 1.0).max()
            + jnp.abs(r - 4.0).max() + jnp.abs(s - 2.0).max())[None]

sp = NamedSharding(mesh, P(("pod", "data", "model")))
xa = jax.device_put(jnp.ones((8, n), jnp.float32), sp)
xr = jax.device_put(jnp.ones((8, r_rows, 2), jnp.float32), sp)
xs = jax.device_put(jnp.ones((8, 64), jnp.float32), sp)

recs = []
with api.tuned(record=recs, mesh_topo=mt):
    sm = shard_map(body, mesh=mesh,
                   in_specs=(P(("pod", "data", "model")),) * 3,
                   out_specs=P(("pod", "data", "model")), check_vma=False)
    dev = jax.block_until_ready(jax.jit(sm)(xa, xr, xs))
t = Trace([TraceEntry(r.cell, r.phase, r.impl) for r in recs])
print(json.dumps({"trace": t.to_jsonl(),
                  "ok": bool(jnp.max(dev) < 1e-5)}))
"""


def _run_child(code, *args, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(_ROOT / "src")
    env.pop("XLA_FLAGS", None)
    # the children model the host tier on forced CPU devices: never let
    # them reach for a chip a parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code, *args],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    if r.returncode != 0:
        raise RuntimeError(f"subprocess failed:\n{r.stderr[-4000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def _topo_dict(t: cm.Topo) -> dict:
    return {"name": t.name, "alpha": t.alpha, "link_bw": t.link_bw,
            "gamma": t.gamma}


def fit_tiers(sizes, count, failures):
    """Fitted base tier + ratio-derived DCN tier (step 1)."""
    fit = _run_child(FIT_SCRIPT, ",".join(str(s) for s in sizes),
                     str(count))
    p = fit["p"]
    ici = cm.fit_topo(p, fit["allgather"], fit["allreduce"],
                      name="host-ici")
    dcn = ici.scaled(name="host-dcn", alpha_mult=cm.DCN_ALPHA_MULT,
                     bw_mult=cm.DCN_BW_MULT)
    emit("hierarchy/fit/axis_size", float(p))
    emit("hierarchy/fit/alpha_us", ici.alpha * 1e6, "host-ici")
    emit("hierarchy/fit/bw_gbps", ici.link_bw / 1e9, "host-ici")
    emit("hierarchy/fit/gamma_ps_per_byte", ici.gamma * 1e12, "host-ici")
    for v, what in ((ici.alpha, "alpha"), (ici.beta, "beta"),
                    (ici.gamma, "gamma")):
        if not (math.isfinite(v) and v >= 0.0):
            failures.append(f"fitted {what} = {v} is not a usable "
                            "fabric parameter")
    if dcn.alpha != ici.alpha * cm.DCN_ALPHA_MULT or \
            dcn.link_bw != ici.link_bw * cm.DCN_BW_MULT:
        failures.append("derived DCN tier does not anchor to the fitted "
                        "absolutes via the published ratios")
    return ici, dcn, fit


def comm_bound_bytes(ici, dcn, cap):
    """Smallest power-of-two payload whose modeled bandwidth term
    dominates the DCN message latency on the must-win cell."""
    b = 1 << 20
    while b < cap and b * ici.beta < 10.0 * dcn.alpha:
        b *= 2
    return min(b, cap)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/BENCH_hierarchy.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (short sweeps, small payload cap)")
    args = ap.parse_args(argv)

    header()
    failures: list[str] = []
    if args.smoke:
        sizes, count, cap = (4096, 65536, 524288), 3, 4 << 20
    else:
        sizes, count, cap = (4096, 16384, 65536, 262144, 1 << 20,
                             4 << 20), 5, 32 << 20

    # -- 1. fit the reachable tier; derive DCN from the fitted absolutes ----
    ici, dcn, fit = fit_tiers(sizes, count, failures)
    mesh = cm.MeshTopo.of(pod=dcn, data=ici, model=ici)
    nbytes = comm_bound_bytes(ici, dcn, cap)
    emit("hierarchy/payload_bytes", float(nbytes),
         "comm-bound by construction" if nbytes < cap else "capped")

    # -- 2. trace the (2,2,2) harness through api dispatch ------------------
    tr = _run_child(TRACE_SCRIPT, json.dumps({
        "ici": _topo_dict(ici), "dcn": _topo_dict(dcn), "nbytes": nbytes}))
    if not tr["ok"]:
        failures.append("(2,2,2) harness collectives returned wrong values")
    trace = Trace.from_jsonl(tr["trace"])
    hier_cells = {c for c in trace.cells() if c.hier}
    flat_cells = {c for c in trace.cells() if not c.hier}
    emit("hierarchy/trace/cells", float(len(trace)),
         f"{len(hier_cells)} hier / {len(flat_cells)} flat")
    if {c.op for c in hier_cells} != set(HIER_IMPL):
        failures.append(f"harness recorded hier ops "
                        f"{sorted(c.op for c in hier_cells)}, expected "
                        f"{sorted(HIER_IMPL)}")
    for c in hier_cells:
        if c.tier != "host-dcn/host-ici" or c.p2 == 0:
            failures.append(f"hier cell {c.op} lost its tier stamp: "
                            f"tier={c.tier!r} p2={c.p2}")

    # -- 3. tune + must-win gates -------------------------------------------
    backend = tuner.CostModelBackend(mesh)
    rep = tuner.tune_trace(trace, backend=backend, min_win=0.05)
    store = next(iter(rep.phase_profiles.values())) \
        if rep.phase_profiles else None
    selections = {}
    for c in sorted(hier_cells, key=lambda c: c.op):
        sel = store.lookup_cell(c) if store is not None else None
        selections[c.op] = sel
        t_def = backend.latency(c, "default")
        t_sel = backend.latency(c, sel) if sel else t_def
        ratio = t_def / t_sel if t_sel else 0.0
        emit(f"hierarchy/select/{c.op}", t_sel * 1e6,
             f"{sel or 'default'} {ratio:.2f}x vs flat ring")
        if sel != HIER_IMPL[c.op]:
            failures.append(
                f"must-win missed: {c.op} cell (p={c.p}, q={c.p2}, "
                f"{c.nbytes}B, {c.tier}) selected {sel!r}, expected "
                f"{HIER_IMPL[c.op]}")
        elif c.op == "allreduce" and ratio < RATIO_GATE:
            failures.append(
                f"hierarchical allreduce win {ratio:.3f}x below the "
                f"{RATIO_GATE}x gate on the DCN-crossing cell")
    for c in flat_cells:
        sel = store.lookup_cell(c) if store is not None else None
        if sel in HIER_IMPL.values():
            failures.append(f"flat cell {c.op}@p{c.p} selected the "
                            f"hierarchical mock-up {sel}")

    # -- 4. the mesh-layout question ----------------------------------------
    # same world, same payload, three ways to lay the sync group out
    # across the DCN boundary; the tuner must put DCN on the OUTER axis
    # of the hierarchy (1/q of the bytes cross it there).
    ar = next(c for c in hier_cells if c.op == "allreduce")
    w = ar.world()
    layouts = {
        "flat-dcn": OpCell("allreduce", w, ar.nbytes, tier=dcn.name),
        "dcn-outer": ar,
        "dcn-inner": OpCell("allreduce", ar.p, ar.nbytes, p2=ar.p2,
                            tier=f"{ici.name}/{dcn.name}"),
    }
    # each layout gets its best LOSSLESS schedule — the wire-quantized
    # family trades precision for bytes, which answers a different
    # question than where to put the DCN boundary
    costs = {}
    n_calls = trace.cells()[ar]
    for name, cell in layouts.items():
        impl, t = min(
            ((nm, t) for nm, t in cm.sweep_cell(cell, mesh).items()
             if math.isfinite(t)
             and REGISTRY[cell.op][nm].wire_dtype is None),
            key=lambda kv: kv[1])
        costs[name] = n_calls * t
        emit(f"hierarchy/layout/{name}_us", costs[name] * 1e6, impl)
    best = min(costs, key=costs.get)
    emit("hierarchy/layout/winner", float(best == "dcn-outer"), best)
    if best != "dcn-outer":
        failures.append(f"mesh-layout question answered {best!r}; the "
                        "DCN-outer hierarchy must minimize the workload")
    if not costs["dcn-outer"] < costs["dcn-inner"] < costs["flat-dcn"]:
        failures.append(f"layout ordering violated: {costs} — expected "
                        "dcn-outer < dcn-inner < flat-dcn")

    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({
        "fit": {"axis_size": fit["p"], "sizes": list(sizes),
                "count": count, "ici": _topo_dict(ici),
                "dcn": _topo_dict(dcn)},
        "payload_bytes": nbytes,
        "trace_cells": len(trace),
        "selections": selections,
        "layout_costs_us": {k: v * 1e6 for k, v in costs.items()},
        "layout_winner": best,
        "failures": failures,
    }, indent=1))

    for f in failures:
        print(f"ERROR: {f}", file=sys.stderr)
    return 1 if failures else 0


def run():
    # benchmarks/run.py entry point: smoke-sized so the suite stays fast
    rc = main(["--smoke"])
    if rc:
        raise RuntimeError("bench_hierarchy smoke failed")


if __name__ == "__main__":
    raise SystemExit(main())
