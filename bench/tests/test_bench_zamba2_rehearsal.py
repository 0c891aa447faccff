"""The zamba2 train cell end to end on one host device at small widths:
a sound run is correct and reports the cell's per-layer counter; the
float8 control, a step that returns its state unchanged and a step that
sees half of its batch come out not correct under the cell's own
limits."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness

CELL = "zamba2-train-1x1"


def rehearse(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-m", "bench.tests.rehearse",
                        "--workload", CELL, *args],
                       cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct():
    r = rehearse("--trace", "1")
    assert r["correct"], r["check"]
    assert r["attempted"] > 3 and r["failed"] == 0
    assert r["metrics"]["recompiles.train"]["value"] == 0
    limits = harness.load_json(harness.BENCH / "limits" / f"{CELL}.json")
    assert set(r["check"]) == set(limits)


@pytest.mark.parametrize("args", [["--control"], ["--fault", "unchanged"],
                                  ["--fault", "half_batch"]],
                         ids=["control_fp8", "state_unchanged", "half_batch"])
def test_control_and_faults_are_not_correct(args):
    r = rehearse(*args)
    assert not r["correct"], r["check"]
