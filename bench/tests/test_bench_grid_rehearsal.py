"""The grid cell end to end on four host devices at a tiny size: a sound
run is correct and prints the contract's last line; the control and each
planted fault come out not correct."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness

KEYS = ["correct", "attempted", "failed", "metrics", "device", "check"]


def rehearse(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-m", "bench.tests.rehearse",
                        "--workload", "collgrid-p4", *args],
                       cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stderr.rstrip().splitlines()[-1].startswith("check grid_bad_elems")
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_and_last_line():
    r = rehearse()
    assert list(r) == KEYS
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"grid_us", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["count"] == 4
    assert r["check"] == {"grid_bad_elems": {"value": 0.0, "limit": 0.0}}


def test_traced_run_reports_no_device_metric_from_the_cpu():
    r = rehearse("--trace", "1")
    assert r["correct"] and r["metrics"] == {}
    assert {"busy_s", "window_s"} <= set(r["device"])


@pytest.mark.parametrize("args", [["--control"], ["--fault", "exchange"],
                                  ["--fault", "answer"]],
                         ids=["control_bf16", "exchange_left_out",
                              "answer_altered"])
def test_control_and_faults_are_not_correct(args):
    r = rehearse(*args)
    assert not r["correct"]
    assert r["check"]["grid_bad_elems"]["value"] > 0
