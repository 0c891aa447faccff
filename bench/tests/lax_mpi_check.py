"""The numpy MPI model against ``jax.lax`` collectives on host devices.

Run as ``python -m bench.tests.lax_mpi_check`` with at least four host
devices (``XLA_FLAGS=--xla_force_host_platform_device_count=4``); prints
one line per op and exits 1 on any disagreement.
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from bench.refs import mpi

P_, ROOT, N, C = 4, 1, 2, 3


def lax_op(op):
    def masked(x):
        return jnp.where(lax.axis_index("x") == ROOT, x, 0)

    def prefix(x, inclusive):
        every = lax.all_gather(x, "x")                    # [p, n, c]
        idx = lax.axis_index("x")
        keep = jnp.arange(P_) <= idx if inclusive else jnp.arange(P_) < idx
        return jnp.sum(jnp.where(keep[:, None, None], every, 0), axis=0)

    return {
        "allgather": lambda x: lax.all_gather(x, "x", tiled=True),
        "allreduce": lambda x: lax.psum(x, "x"),
        "reducescatter": lambda x: lax.psum_scatter(x, "x", tiled=True),
        "alltoall": lambda x: lax.all_to_all(x, "x", 0, 0, tiled=True),
        "bcast": lambda x: lax.psum(masked(x), "x"),
        "gather": lambda x: lax.all_gather(x, "x", tiled=True),
        "scatter": lambda x: lax.dynamic_slice_in_dim(
            lax.psum(masked(x), "x"), lax.axis_index("x") * N, N),
        "reduce": lambda x: lax.psum(x, "x"),
        "scan": lambda x: prefix(x, True),
        "exscan": lambda x: prefix(x, False),
    }[op]


def main():
    mesh = Mesh(np.asarray(jax.devices()[:P_]), ("x",))
    rng = np.random.default_rng(0)
    bad = 0
    for op in mpi.REFERENCE_OPS:
        rows = P_ * N if op in mpi.V_IN else N
        x = [rng.integers(-1024, 1025, (rows, C)).astype(np.float32)
             for _ in range(P_)]
        f = jax.jit(jax.shard_map(lax_op(op), mesh=mesh, in_specs=P("x"),
                                  out_specs=P("x"), check_vma=False))
        got = np.split(np.asarray(f(np.concatenate(x))), P_)
        want = mpi.expected(op, x, ROOT)
        ok = all(np.array_equal(g, w) for g, w in zip(got, want)
                 if w is not None)
        bad += not ok
        print(op, "ok" if ok else "DIFFERS")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
