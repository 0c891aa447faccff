"""The program's own tracing (``repro.obs``): the named scopes reach the
compiled train step's ``op_name`` metadata, forward and backward; the
compile counter sees a new shape and nothing else; the operator's capture
in ``launch/train.py`` writes a profile that holds the host spans; and on
four host devices a dispatched mock-up carries ``pgtune.<op>.<impl>``
while the grid driver still attributes every call."""
import glob
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import scopes
from bench.trace import hlo_op_names
from repro import obs
from repro.configs import get_config
from repro.data import make_batch
from repro.launch.mesh import make_host_mesh
from repro.train import Trainer

ROOT = pathlib.Path(__file__).resolve().parents[1]

# arch -> (scopes the step must hold, scopes it must hold forward and
# backward)
SCOPES = {
    "rwkv6-3b": ({"embed", "head", "optimizer", "grad_sync", "layers",
                  "rwkv", "wkv", "intra", "state"},
                 {"wkv", "intra", "state", "head", "embed"}),
    "zamba2-1.2b": ({"mamba", "ssd", "intra", "state", "shared_attn", "head",
                     "optimizer"},
                    {"ssd", "intra", "state", "shared_attn"}),
    "llama3.2-3b": ({"attn", "head", "optimizer"}, {"attn"}),
}


def _step_op_names(arch):
    cfg = get_config(arch).smoke()
    tr = Trainer(cfg, mesh=make_host_mesh((1, 1), ("data", "model")))
    params, opt = tr.init(0)
    batch = tr.put_batch(make_batch(cfg, 2, 32, 0))
    with tr._tuned():
        text = tr._step.lower(params, opt, batch,
                              jnp.asarray(0, jnp.int32)).compile().as_text()
    return hlo_op_names(text)


@pytest.mark.parametrize("arch", sorted(SCOPES))
def test_scopes_reach_the_compiled_step(arch):
    names = _step_op_names(arch)
    want, both = SCOPES[arch]
    fwd, bwd = set(), set()
    for stack in names.values():
        (bwd if "transpose" in stack else fwd).update(scopes.scopes_of(stack))
    assert want <= fwd | bwd, want - (fwd | bwd)
    assert both <= fwd, both - fwd
    assert both <= bwd, both - bwd
    # the WKV scope is not the block's: "rwkv" holds "wkv" as letters only
    if arch == "rwkv6-3b":
        assert any("rwkv" in scopes.scopes_of(s)
                   and "wkv" not in scopes.scopes_of(s)
                   for s in names.values())
    # the SSD scan's parts sit inside its scope: ssd/intra, ssd/state
    if arch == "zamba2-1.2b":
        for part in ("intra", "state"):
            assert any({"ssd", part} <= scopes.scopes_of(s)
                       for s in names.values()), part
    # each dispatch site names its op and the impl chosen
    assert any(p.startswith("pgtune.") and p.endswith(".default")
               for s in names.values() for p in scopes.scopes_of(s))


def test_compile_counter_counts_a_new_shape_once():
    cfg = get_config("rwkv6-3b").smoke()
    tr = Trainer(cfg)
    params, opt = tr.init(0)
    built0, secs0 = obs.compiles()
    for i in range(3):
        params, opt, m = tr.step(params, opt,
                                 tr.put_batch(make_batch(cfg, 2, 32, i)), i)
    jax.block_until_ready(m["loss"])
    built1, secs1 = obs.compiles()
    assert built1 - built0 == 1 and secs1 > secs0
    assert tr.recompiles == 0 and tr.recompile_step is None
    params, opt, m = tr.step(params, opt,
                             tr.put_batch(make_batch(cfg, 2, 16, 3)), 3)
    jax.block_until_ready(m["loss"])
    assert tr.recompiles == 1 and tr.recompile_step == 3
    assert obs.compiles()[0] - built1 == 1


def _host_span_names(trace_dir):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [e.name for e in line.events]
    return out


def _env(**kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]),
               **kw)
    env.pop("XLA_FLAGS", None)
    return env


def test_operator_capture_holds_the_step_spans(tmp_path):
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "rwkv6-3b",
         "--smoke", "--steps", "4", "--global-batch", "2", "--seq", "32",
         "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-every", "2",
         "--log-every", "1", "--trace-dir", str(tmp_path / "trace"),
         "--trace-steps", "1:2"],
        cwd=ROOT, env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc")),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "done: 4 steps" in p.stdout and "recompile:" not in p.stdout
    names = _host_span_names(str(tmp_path / "trace"))
    assert names.count("train.step") == 2
    assert names.count("train.put_batch") == 2
    assert names.count("ckpt.save") == 1        # after step 1: step 2
    assert "train.wait" in names


SPMD_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from bench import harness
from bench.kinds import grid
from bench.scopes import scopes_of
from bench.trace import hlo_op_names
from repro.core import api

out = {}
mesh = Mesh(np.asarray(jax.devices()[:4]), ("x",))
fn = jax.jit(jax.shard_map(lambda a: api.allgather(a, "x"), mesh=mesh,
                           in_specs=P("x"), out_specs=P("x"),
                           check_vma=False))
x = jnp.arange(4 * 8 * 128, dtype=jnp.float32).reshape(32, 128)
with api.tuned(force={"allgather": "allgather_as_ring"}):
    text = fn.lower(x).compile().as_text()
out["forced"] = sorted({p for s in hlo_op_names(text).values()
                        for p in scopes_of(s) if p.startswith("pgtune.")})

bm = harness.load_json(harness.ROOT / "bench" / "tests" / "staged.json")
cfg = harness.load_json(harness.ROOT / bm["configs"][0]["file"])
traffic = harness.load_json(harness.ROOT / "bench" / "traffic"
                            / (bm["workloads"][0]["traffic"] + ".json"))
traffic = dict(traffic, payload_bytes=[8, 64, 512, 4096],
               ladders_per_launch=3)
cell = grid.Cell(cfg, traffic, {"grid_bad_elems": 0}, seed=2**31 + 5,
                 devices=jax.devices()[:4])
cell.setup()
names = hlo_op_names(cell.compiled.as_text())
out["calls"] = len(cell.calls)
out["attributed"] = sorted(set(cell._call_of.values()))
# instructions that carry a call's c<i> scope and a dispatch scope: their
# call as the driver attributes it, and as their own scope names it
pairs = []
for n, s in names.items():
    sc = scopes_of(s)
    ci = [p for p in sc if len(p) == 4 and p[0] == "c" and p[1:].isdigit()]
    if ci and any(p.startswith("pgtune.") for p in sc):
        pairs.append([int(ci[0][1:]), cell.call_of(n)])
out["pairs"] = pairs
out["impls"] = sorted({p for s in names.values() for p in scopes_of(s)
                       if p.startswith("pgtune.")})
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def spmd():
    p = subprocess.run([sys.executable, "-c", SPMD_SCRIPT], cwd=ROOT,
                       env=_env(), capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_forced_mockup_carries_its_dispatch_scope(spmd):
    assert spmd["forced"] == ["pgtune.allgather.allgather_as_ring"]


def test_grid_attributes_every_call_under_dispatch_scopes(spmd):
    assert spmd["attributed"] == list(range(spmd["calls"]))
    assert spmd["pairs"] and all(c == a for c, a in spmd["pairs"])
    ops = {s.split(".")[1] for s in spmd["impls"]}
    assert len(ops) == 10, spmd["impls"]
