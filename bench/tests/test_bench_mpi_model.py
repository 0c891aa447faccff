"""The numpy MPI model (``bench/refs/mpi.py``) agrees with ``jax.lax``
collectives on four host devices, and tells wrong answers apart."""
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.refs import mpi


def test_model_matches_lax_on_four_host_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-m", "bench.tests.lax_mpi_check"],
                       cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stdout + p.stderr
    assert p.stdout.count(" ok") == len(mpi.REFERENCE_OPS)


@pytest.mark.parametrize("op", mpi.REFERENCE_OPS)
def test_model_by_hand(op):
    x = [np.full((4, 1), r + 1, np.float32) for r in range(4)]  # rank r: r+1
    x = [a * np.arange(1, 5, dtype=np.float32)[:, None] for a in x]
    out = mpi.expected(op, x, root=2)
    if op in ("gather", "reduce"):
        assert [o is None for o in out] == [True, True, False, True]
    if op == "exscan":
        assert out[0] is None
        assert np.array_equal(out[3], x[0] + x[1] + x[2])
    if op == "alltoall":       # rank 1 receives row 1 of every rank
        assert out[1].ravel().tolist() == [2, 4, 6, 8]
    if op == "scatter":        # rank 3 receives row 3 of the root (rank 2)
        assert out[3].ravel().tolist() == [12]
    if op == "reducescatter":  # rank 0 receives row 0 summed: 1+2+3+4
        assert out[0].ravel().tolist() == [10]
