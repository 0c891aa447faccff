"""Multi-device SPMD validation (subprocess — keeps this process at 1 dev).

1. core selfcheck: every mock-up through real shard_map on 8 host devices.
2. SPMD equivalence: identical params + batch on 1 device vs a (data=2,
   model=4) mesh produce the same loss and updated params.
3. Pod-axis equivalence: the same check on a (pod, data, model) = (2, 2, 2)
   mesh, exercising the hierarchical RS(data)→AR(pod) gradient sync.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")

EQUIV_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import lm
from repro.models.params import tree_pspecs
from repro.train.trainer import make_step_fns, opt_state_pspecs
from repro.data import make_batch
from repro.launch.mesh import make_host_mesh

arch = sys.argv[1]
# mesh spec "2x4" -> (data, model); "2x2x2" -> (pod, data, model)
shape = tuple(int(x) for x in (sys.argv[2] if len(sys.argv) > 2
                               else "2x4").split("x"))
axes = ("pod", "data", "model")[-len(shape):]
tp = shape[-1]
dp_axes = axes[:-1]

cfg = get_config(arch).smoke()
init_fn, train_fn = make_step_fns(cfg, n_micro=1)
params1, opt1 = jax.jit(init_fn)(jax.random.key(7))
batch1 = {k: jnp.asarray(v) for k, v in make_batch(cfg, 8, 16, 0).items()}
p1, o1, m1 = jax.jit(train_fn)(params1, opt1, batch1, jnp.int32(50))

mesh = make_host_mesh(shape, axes)
specs = lm.model_specs(cfg, tp=tp)
pspecs = tree_pspecs(specs)
opt_ps = opt_state_pspecs(cfg.optimizer, specs)
put = lambda t, ps: jax.tree.map(
    lambda x, p: jax.device_put(np.asarray(x), NamedSharding(mesh, p)), t, ps)
params8, opt8 = put(params1, pspecs), put(opt1, opt_ps)
batch8 = jax.tree.map(lambda x: jax.device_put(
    np.asarray(x), NamedSharding(mesh, P(dp_axes))), batch1)
sm = shard_map(train_fn, mesh=mesh,
               in_specs=(pspecs, opt_ps,
                         jax.tree.map(lambda _: P(dp_axes), batch1), P()),
               out_specs=(pspecs, opt_ps,
                          {"loss": P(), "grad_norm": P(), "lr": P()}),
               check_vma=False)
p8, o8, m8 = jax.jit(sm)(params8, opt8, batch8, jnp.int32(50))
dl = abs(float(m1["loss"]) - float(m8["loss"]))
dp = max(float(np.max(np.abs(np.asarray(a, np.float32)
                             - np.asarray(b, np.float32))))
         for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p8)))
dg = abs(float(m1["grad_norm"]) / float(m8["grad_norm"]) - 1.0)

# a sharded init must give every replica of a leaf the same values
pi, _ = jax.jit(shard_map(init_fn, mesh=mesh, in_specs=P(),
                          out_specs=(pspecs, opt_ps), check_vma=False))(
    jax.random.key(7))
split = []
for path, leaf in jax.tree.flatten_with_path(pi)[0]:
    held = {}
    for s in leaf.addressable_shards:
        held.setdefault(str(s.index), []).append(np.asarray(s.data))
    if any(not np.array_equal(v[0], a) for v in held.values() for a in v):
        split.append(jax.tree_util.keystr(path))
import json
print(json.dumps({"dl": dl, "dp": dp, "dg": dg, "split": split}))
"""


def _run(code, *args, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", code, *args],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)


def test_selfcheck_all_mockups_spmd_8dev():
    r = _run("import sys; from repro.core.selfcheck import main; "
             "sys.exit(main(['--devices', '8', '--json']))")
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["failures"] == []
    assert out["total"] >= 40


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["llama3.2-3b", "phi3.5-moe-42b-a6.6b",
                                  "rwkv6-3b", "zamba2-1.2b"])
def test_spmd_equivalence(arch):
    r = _run(EQUIV_SCRIPT, arch)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # updated params (live LR at step 50) are the strict criterion for
    # dense archs; the loss metric is bf16-reduction-order noisy, and MoE
    # archs legitimately differ through capacity drops per batch split
    moe = "moe" in arch or "deepseek" in arch
    # MoE capacity is derived from LOCAL token counts, so the batch split
    # changes which tokens drop — the loss gap is real routing noise, not a
    # collective bug; the tight params bound below is the strict check
    # (observed ~5e-4 on this seed) so a real collective regression still
    # trips even with the looser loss tolerance.
    assert out["dl"] < (1e-1 if moe else 1e-2), out
    assert out["dp"] < 5e-2, out
    # the grad-norm metric counts each global element once, replicas or not
    assert out["dg"] < (1e-1 if moe else 1e-2), out
    assert out["split"] == [], out


FUSED_MM_SCRIPT = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core import api
from repro.dist import ops

mesh = Mesh(np.array(jax.devices()), ("model",))
p, n, k, m = 4, 8, 16, 12
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(p * n, k)).astype(np.float32))
xb = jnp.asarray(rng.normal(size=(p * p * n, k)).astype(np.float32))
w = jnp.asarray(rng.normal(size=(k, m)).astype(np.float32))
cot = lambda y: jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(y.shape)

def run(f, xin, force):
    def body(a):
        val = f(a)
        g = jax.grad(lambda b: jnp.sum(f(b) * cot(f(b))))(a)
        return val, g
    sm = shard_map(body, mesh=mesh, in_specs=P("model"),
                   out_specs=(P("model"), P("model")), check_vma=False)
    with api.tuned(force=force):
        val, g = jax.jit(sm)(xin)
    return np.asarray(val), np.asarray(g)

out = {}
for op_name, f, xin in [
        ("agmm", lambda a: ops.allgather_matmul(a, w, "model"), x),
        ("mmrs", lambda a: ops.matmul_reducescatter(a, w, "model"), xb)]:
    vd, gd = run(f, xin, {"allgather_matmul": "default",
                          "matmul_reducescatter": "default"})
    vf, gf = run(f, xin, {"allgather_matmul": "fused_ring",
                          "matmul_reducescatter": "fused_ring"})
    out[op_name] = {"dv": float(np.abs(vd - vf).max()),
                    "dg": float(np.abs(gd - gf).max())}
# oracle: fused allgather_matmul vs dense numpy
vf, _ = run(lambda a: ops.allgather_matmul(a, w, "model"), x,
            {"allgather_matmul": "fused_ring"})
want = np.asarray(x) @ np.asarray(w)
out["oracle_agmm"] = float(np.abs(
    vf.reshape(p, p * n, m) - want[None]).max())

# matmul_accumulate (contraction-dim ring) over a data axis: w K-sharded,
# x shard-local; compare fused vs unfused values + weight grads, and the
# REWIRED col_matmul(fsdp_dim=0) K-gather site vs the legacy composition
# bit-for-bit under default dispatch (the acceptance criterion).  Integer-
# valued operands and cotangent keep every reduction order exact, so the
# comparison sees the composition and not the order XLA picks per dot.
mesh_d = Mesh(np.array(jax.devices()), ("data",))
kloc, T, M = 4, 6, 5
K = p * kloc
xs = jnp.asarray(np.round(rng.normal(size=(T, K)) * 2).astype(np.float32))
wacc = jnp.asarray(np.round(rng.normal(size=(K, M)) * 2).astype(np.float32))
cot_i = lambda y: jnp.round(cot(y) * 4)

def run_acc(f, force):
    def body(wb):
        val = f(wb)
        g = jax.grad(lambda b: jnp.sum(f(b) * cot_i(f(b))))(wb)
        return val, g
    sm = shard_map(body, mesh=mesh_d, in_specs=P("data"),
                   out_specs=(P(), P("data")), check_vma=False)
    with api.tuned(force=force):
        val, g = jax.jit(sm)(wacc)
    return np.asarray(val), np.asarray(g)

acc_f = lambda wb: ops.matmul_accumulate(xs, wb, "data")
acc_u = lambda wb: jnp.matmul(xs, ops.fsdp_gather(wb, 0, "data"))
vd, gd = run_acc(acc_u, {})
vf_, gf_ = run_acc(acc_f, {"matmul_accumulate": "fused_ring",
                           "matmul_reducescatter": "fused_ring"})
v0, g0 = run_acc(acc_f, {})          # default dispatch = unfused comp
out["acc"] = {"dv": float(np.abs(vd - vf_).max()),
              "dg": float(np.abs(gd - gf_).max())}
out["acc_default_bitexact"] = bool((vd == v0).all() and (gd == g0).all())
out["oracle_acc"] = float(np.abs(vd - np.asarray(xs) @ np.asarray(wacc)
                                 ).max())

col_f = lambda wb: ops.col_matmul(xs, wb, "model", fsdp_dim=0)
col_u = lambda wb: ops.col_matmul(xs, ops.fsdp_gather(wb, 0, "data"),
                                  "model")
vcf, gcf = run_acc(col_f, {})
vcu, gcu = run_acc(col_u, {})
out["col_rewired_bitexact"] = bool((vcf == vcu).all()
                                   and (gcf == gcu).all())
print(json.dumps(out))
"""


ROW2D_SCRIPT = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core import api
from repro.dist import ops

# (data, model) = (2, 2) mesh; integer-valued operands + cotangent keep
# every reduction order exactly representable -> bit-exact comparisons
d, q = 2, 2
mesh = Mesh(np.array(jax.devices()).reshape(d, q), ("data", "model"))
T, Kl, ml = 4, 3, 5                  # per-shard x [T, Kl], w [Kl, ml]
rng = np.random.default_rng(7)
X = jnp.asarray(np.round(rng.normal(size=(d * T, q * Kl)) * 2)
                .astype(np.float32))
W = jnp.asarray(np.round(rng.normal(size=(q * Kl, d * ml)) * 2)
                .astype(np.float32))

def cot(y):
    return jnp.round(jnp.cos(jnp.arange(y.size, dtype=jnp.float32))
                     .reshape(y.shape) * 4)

def run(fun, force):
    def body(xs, ws):
        y = fun(xs, ws)
        gx, gw = jax.grad(lambda a, b: jnp.sum(fun(a, b) * cot(y)),
                          argnums=(0, 1))(xs, ws)
        return y, gx, gw
    sm = shard_map(body, mesh=mesh,
                   in_specs=(P("data", "model"), P("model", "data")),
                   out_specs=(P("data", None), P("data", "model"),
                              P("model", "data")),
                   check_vma=False)
    with api.tuned(force=force) as ctx:
        y, gx, gw = jax.jit(sm)(X, W)
    return (np.asarray(y), np.asarray(gx), np.asarray(gw),
            [(r.op, r.cell.p, r.cell.p2, r.cell.mm_role, r.impl, r.phase)
             for r in ctx.record])

new = lambda a, b: ops.row_matmul(a, b, "model", fsdp_dim=1)
leg = lambda a, b: ops.tp_allreduce(ops.fsdp_matmul(a, b, "data"), "model")

y0, gx0, gw0, rec0 = run(new, {})
yl, gxl, gwl, recl = run(leg, {})
yf, gxf, gwf, recf = run(new, {"matmul_reducescatter_2d": "fused_ring2d",
                               "allgather_matmul": "fused_ring"})
out = {
  "default_bitexact": bool((y0 == yl).all() and (gx0 == gxl).all()
                           and (gw0 == gwl).all()),
  "fused_bitexact": bool((yf == yl).all() and (gxf == gxl).all()
                         and (gwf == gwl).all()),
  "oracle": float(np.abs(y0 - np.asarray(X) @ np.asarray(W)).max()),
  "cells_2d": [r for r in rec0 if r[0] == "matmul_reducescatter_2d"],
  "fused_impls": sorted({(r[0], r[4]) for r in recf
                         if r[0] == "matmul_reducescatter_2d"}),
  "monolithic_ar": any(r[0] == "allreduce" for r in rec0),
  "legacy_ar": any(r[0] == "allreduce" for r in recl),
}
print(json.dumps(out))
"""


MEASURED_REPLAY_SCRIPT = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from repro.core import tuner
from repro.core.trace import Trace, TraceEntry

# a (2,2)-world 2-D cell IS replayable on the 4 host devices (the measured
# backend builds the 2-axis mesh); the p=8 1-D cell is not and notes out
t = Trace([TraceEntry.of("allreduce", 4, 1024, "decode", "default", 5),
           TraceEntry.of("allreduce", 8, 1024, "decode", "default", 5),
           TraceEntry.of("matmul_reducescatter_2d", 2, 2 * 64 * 6 * 4,
                         "decode", "default", 3, mm_k=64, mm_m=8,
                         mm_n=2 * 6, mm_role="2d", p2=2)])
backend = tuner.MeasuredBackend(K=2, max_nrep=3)
rep = tuner.tune_trace(t, backend=backend)
# a backend on a 2-device sub-mesh measures the p=2 cell of a 2x2 step,
# at the 2x2 step's own sub-axis size, and skips the p=4 cell
t2 = Trace([TraceEntry.of("allreduce", 2, 4096, "bwd", "default", 3),
            TraceEntry.of("allreduce", 4, 4096, "bwd", "default", 3)])
sub2 = tuner.tune_trace(t2, backend=tuner.MeasuredBackend(
    K=2, max_nrep=3, axis_size=2), min_win=0.0)
print(json.dumps({
    "sup": backend.supported_axis_size,
    "n_meas": len(rep.measurements),
    "n_meas_2d": sum(1 for m in rep.measurements
                     if m.cell.op == "matmul_reducescatter_2d"),
    "skips": [n for n in rep.notes if "host axis size" in n],
    "est_default": rep.est_default_s.get("decode", 0.0),
    "sub2_p": sorted({m.cell.p for m in sub2.measurements}),
    "sub2_skips": [n for n in sub2.notes if "host axis size 2" in n],
}))
"""


@pytest.mark.slow
def test_fused_collective_matmul_spmd_equivalence_4dev():
    """All THREE fused rings (allgather-matmul / matmul-reducescatter /
    matmul-accumulate) vs the unfused composition under REAL shard_map on
    4 host devices — values and grads; the rewired col_matmul(fsdp_dim=0)
    K-gather site must match the legacy fsdp_gather composition
    BIT-FOR-BIT under default dispatch (acceptance criterion)."""
    r = _run(FUSED_MM_SCRIPT)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["agmm"]["dv"] < 1e-4 and out["agmm"]["dg"] < 1e-4, out
    assert out["mmrs"]["dv"] < 1e-4 and out["mmrs"]["dg"] < 1e-4, out
    assert out["oracle_agmm"] < 1e-4, out
    assert out["acc"]["dv"] < 1e-4 and out["acc"]["dg"] < 1e-4, out
    assert out["oracle_acc"] < 1e-4, out
    assert out["acc_default_bitexact"] is True, out
    assert out["col_rewired_bitexact"] is True, out


@pytest.mark.slow
def test_row_matmul_2d_spmd_equivalence_4dev():
    """Acceptance: on a REAL (data, model) = (2, 2) shard_map mesh,
    row_matmul(fsdp_dim=1) through `matmul_reducescatter_2d` — under
    default dispatch AND forced fused_ring2d — is bit-exact (fwd and
    grads) vs the legacy tp_allreduce(fsdp_matmul(...)) composition, the
    recorded cells carry the 2-D geometry (p=2, p2=2, roles 2d/2dT), and
    the monolithic model-axis allreduce is GONE from the rewired path."""
    r = _run(ROW2D_SCRIPT)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["default_bitexact"] is True, out
    assert out["fused_bitexact"] is True, out
    assert out["oracle"] == 0.0, out
    roles = {(c[1], c[2], c[3], c[5]) for c in out["cells_2d"]}
    assert (2, 2, "2d", "fwd") in roles, out
    assert (2, 2, "2dT", "bwd") in roles, out      # the fused transpose dw
    assert out["fused_impls"] == [["matmul_reducescatter_2d",
                                   "fused_ring2d"]], out
    assert out["monolithic_ar"] is False, out      # ROADMAP item closed
    assert out["legacy_ar"] is True, out           # ...and it WAS there


@pytest.mark.slow
def test_measured_backend_trace_replay_4dev():
    """ROADMAP item: replay a recorded trace's cells on real host devices —
    the p=4 cell AND the (2,2)-world 2-D cell are wall-clock measured
    (both impls each), the p=8 cell skips with a note; a backend on a
    2-device sub-mesh measures p=2 cells and skips the p=4 one."""
    r = _run(MEASURED_REPLAY_SCRIPT)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["sup"] == 4
    assert out["n_meas"] > 0                 # p=4 cell actually measured
    assert out["n_meas_2d"] >= 2, out        # 2-D replay on the 2x2 mesh
    assert out["skips"], out                 # p=8 cell noted as skipped
    assert out["est_default"] > 0.0
    assert out["sub2_p"] == [2], out
    assert len(out["sub2_skips"]) == 1, out


@pytest.mark.slow
def test_spmd_equivalence_pod_axis():
    """ROADMAP's real-`pod`-axis coverage: an 8-device (pod, data, model)
    = (2, 2, 2) mesh — batch split over pod AND data, params FSDP-sharded
    over data only, grads synced via the hierarchical RS(data)→AR(pod)
    schedule — must match the unsharded 1-device step."""
    r = _run(EQUIV_SCRIPT, "llama3.2-3b", "2x2x2")
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["dl"] < 1e-2, out
    assert out["dp"] < 5e-2, out
    assert out["dg"] < 1e-2, out
    assert out["split"] == [], out

