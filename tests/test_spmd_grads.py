"""Per-leaf gradients of the sharded step equal the one-device gradients.

A (data=2, model=4) mesh of 8 host devices (subprocess; this process stays
at one device) against one device, in f32, with the same parameters and
batch: every leaf's synchronized mean gradient must match the one-device
gradient to 1e-4 of its norm, and every replica of a replicated leaf must
hold the same gradient.  A leaf whose gradient is counted once per model
shard, or lost on all but one, is off by a factor of 2 to 4; the global
grad norm hides such an error on a small leaf.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

GRADS_SCRIPT = r"""
import dataclasses, json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs import get_config
from repro.data import make_batch
from repro.launch.mesh import make_host_mesh
from repro.train.gradcheck import leaf_grad_check
from repro.train.trainer import make_step_fns

cfg = dataclasses.replace(get_config(sys.argv[1]).smoke(), dtype="float32")
params, _ = jax.jit(make_step_fns(cfg)[0])(jax.random.key(3))
out = leaf_grad_check(cfg, params, make_batch(cfg, 8, 16, 0),
                      make_host_mesh((2, 4), ("data", "model")))
print(json.dumps(out))
"""


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["llama3.2-3b", "rwkv6-3b", "zamba2-1.2b",
                                  "phi3.5-moe-42b-a6.6b", "whisper-medium"])
def test_sharded_leaf_grads_match_one_device(arch):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", GRADS_SCRIPT, arch],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert abs(out["loss1"] - out["loss"]) < 1e-5, out["loss1"]
    bad = {k: v for k, v in out["rel"].items() if not v < 1e-4}
    assert not bad, bad
    assert out["split"] == [], out["split"]
