"""Every lossless flat mock-up runs inside a sharded training step
(subprocess on 4 host devices; this process stays at one device)."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

FORCED_STEP_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
from repro.configs import get_config
from repro.core import collectives as C
from repro.data import make_batch
from repro.launch.mesh import make_host_mesh
from repro.train import Trainer

op = sys.argv[1]
cfg = get_config("rwkv6-3b").smoke()
mesh = make_host_mesh((2, 2), ("data", "model"))
batch = make_batch(cfg, 4, 16, 0)

def step0(force):
    record = []
    tr = Trainer(cfg, mesh=mesh, force=force, record=record)
    params, opt = tr.init(0)
    _, _, m = tr.step(params, opt, tr.put_batch(batch), 0)
    return float(m["loss"]), sorted({r.impl for r in record if r.op == op})

base, _ = step0(None)
out = {}
for name, impl in C.REGISTRY[op].items():
    if name == "default" or impl.wire_dtype or impl.hier:
        continue
    try:
        loss, used = step0({op: name})
        out[name] = {"dl": abs(loss - base), "used": used}
    except Exception as e:
        out[name] = {"error": str(e).splitlines()[0][:300]}
print(json.dumps(out))
"""


@pytest.mark.slow
@pytest.mark.parametrize("op", ["allgather", "allreduce", "reducescatter"])
def test_lossless_mockups_run_in_2x2_train_step(op):
    """Every lossless flat mock-up of ``op``, forced at all of its sites,
    compiles and runs inside the bf16 rwkv6-3b training step on a 2x2
    (data, model) mesh, and gives the default's step-0 loss.  XLA:CPU once
    refused the all-to-all allgather there (operand layouts)."""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", FORCED_STEP_SCRIPT, op],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out, out
    for name, res in out.items():
        assert "error" not in res, (name, res)
        assert res["used"] == [name], (name, res)
        # the mock-ups reorder bf16 sums; a shard dropped or doubled moves
        # the loss by O(0.1)
        assert res["dl"] < 1e-2, (name, res)
