"""repro.dist — the sharded "application" layer over the tuned dispatcher.

* ``repro.dist.axes`` — mesh-axis registry (``AXES``, ``has_axis``,
  ``axis_size_or_1``)
* ``repro.dist.ops``  — custom-VJP model-parallel primitives whose forward
  and backward collectives all dispatch through ``repro.core.api``

This package is the repo's equivalent of MPI user code: models call
``dist.ops``; ``core.api`` is the PMPI interposition layer that redirects
each call to the best guideline mock-up.
"""
from repro.dist import ops  # noqa: F401
from repro.dist.axes import AXES, MeshAxes, axis_size_or_1, has_axis  # noqa: F401
from repro.dist.ops import (allgather_matmul, col_matmul,  # noqa: F401
                            ep_alltoall, fsdp_gather, fsdp_matmul,
                            matmul_accumulate, matmul_reducescatter,
                            matmul_reducescatter_2d, row_matmul,
                            tp_allgather_split,
                            tp_allreduce, tp_copy,
                            tp_psum_grad, tp_reducescatter)
