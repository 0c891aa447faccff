"""Measured-latency backend: ReproMPI's Algorithm 1 on JAX host devices.

Timing procedure (paper Algorithm 1): synchronize, t = now, run collective,
record t' - t.  The dissemination-barrier analogue here is a jitted 1-element
psum executed (and blocked on) before every sample; collectives themselves
are pre-compiled so only execution is timed.

Replay is keyed on the full ``OpCell``: a fused collective-matmul cell is
re-executed with the *recorded* GEMM — dtype and ``(mm_k, mm_m, mm_n)``
exactly as the callsite issued them — not a canonical square weight, so
wall-clock replay prices the actual matmul.  Fused cells without recorded
geometry (v1 traces) cannot be replayed; the tuner note-skips them.

A cell of world ``w`` runs on the first ``w`` devices the process sees
(CPU host devices in tests, TPU chips on a chip host), so the sub-axis
cells of a multi-axis mesh (``p = 2`` on a 2x2 host) are measured on a
sub-mesh of that size.  Its absolute numbers are CPU-flavored; the tuner uses it to
validate *orderings* and to exercise the full offline-tuning pipeline, while
production-scale decisions use ``core.costmodel``.
"""
from __future__ import annotations

import time
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.core import collectives as C
from repro.core.cell import OpCell

AXIS = "bench"
AXIS2 = "bench2"       # inner axis of the 2-D replay mesh

#: ops whose cells carry a fused-matmul geometry the replay must honor
MATMUL_OPS = ("allgather_matmul", "matmul_reducescatter", "matmul_accumulate",
              "matmul_reducescatter_2d")


def _devices(world: int) -> np.ndarray:
    devs = jax.devices()
    if world > len(devs):
        raise ValueError(f"replay needs {world} devices, have {len(devs)}")
    return np.array(devs[:world])


@lru_cache(maxsize=8)
def _mesh(p: int | None = None) -> Mesh:
    """The 1-D replay mesh over the first ``p`` devices (default: all)."""
    return Mesh(_devices(p or len(jax.devices())), (AXIS,))


@lru_cache(maxsize=8)
def _mesh2(p: int, p2: int) -> Mesh:
    """The (outer, inner) replay mesh of a 2-D cell over the first
    ``p * p2`` devices."""
    return Mesh(_devices(p * p2).reshape(p, p2), (AXIS, AXIS2))


def axis_size() -> int:
    """Number of devices the process sees: the largest replay world."""
    return len(jax.devices())


def host_cell(op: str, nbytes: int, *, dtype: str = "float32",
              **geom) -> OpCell:
    """An ``OpCell`` at the axis size the host devices form (benchmarks)."""
    return OpCell(op, axis_size(), nbytes, dtype, **geom)


def problem_shapes(cell: OpCell) -> dict[str, tuple[int, ...]]:
    """Per-shard operand shapes the replay builds for ``cell`` — pure
    function of the cell, unit-testable without devices.

    ``x`` is the sharded operand (the collective payload), ``w`` the
    shard-local second operand of the fused ops (absent for plain
    collectives).  Fused shapes come from the RECORDED GEMM dims.
    """
    p = cell.p
    if cell.op in MATMUL_OPS:
        if not cell.fused:
            raise ValueError(
                f"cell {cell} has no recorded matmul geometry; a fused op "
                "cannot be replayed without it (v1 trace?)")
        if cell.op == "matmul_reducescatter_2d":
            q = max(cell.p2, 1)
            if cell.mm_role == "2dT":
                # payload = the cotangent row block [mm_k/p, mm_m]; its
                # cols must divide the inner rs axis; x is shard-local
                t_loc = max(1, cell.mm_k // p)
                m_pad = max(q, (cell.mm_m // q) * q)
                return {"x": (t_loc, m_pad),
                        "w": (p * t_loc, cell.mm_n)}
            # payload = the weight column block [mm_k, mm_n/p]; the
            # shard-local x rows must divide the inner rs axis
            rows = max(q, (cell.mm_m // q) * q)
            return {"x": (cell.mm_k, max(1, cell.mm_n // p)),
                    "w": (rows, cell.mm_k)}
        if cell.op == "allgather_matmul":
            return {"x": (max(1, cell.mm_m // p), cell.mm_k),
                    "w": (cell.mm_k, cell.mm_n)}
        if cell.op == "matmul_reducescatter":
            rows = max(p, (cell.mm_m // p) * p)   # psum_scatter must divide
            return {"x": (rows, cell.mm_k), "w": (cell.mm_k, cell.mm_n)}
        # matmul_accumulate: the payload is the K-dim weight shard
        k_loc = max(1, cell.mm_k // p)
        return {"x": (k_loc, cell.mm_n), "w": (cell.mm_m, p * k_loc)}
    itemsize = cell.itemsize
    n_rows = max(1, cell.nbytes // itemsize)
    if cell.op in ("alltoall", "reducescatter", "scatter"):
        # v-style ops: nbytes is the per-chunk payload, input is one chunk
        # per rank of the (possibly hierarchical) group
        n_rows *= cell.world()
    return {"x": (n_rows, 1)}


@lru_cache(maxsize=512)
def _compiled(cell: OpCell, impl: str):
    if cell.op == "matmul_reducescatter_2d":
        return _compiled_2d(cell, impl)
    if cell.hier:
        return _compiled_hier(cell, impl)
    mesh = _mesh(cell.p)
    p = cell.p
    fn = C.REGISTRY[cell.op][impl].fn
    shapes = problem_shapes(cell)
    dt = jnp.dtype(cell.dtype if cell.dtype else "float32")

    if cell.op == "matmul_accumulate":
        # streamed operand = the weight shard; the stationary x is a
        # shard-local closure constant with the recorded [mm_m, mm_k]
        stat = jnp.ones(shapes["w"], dt)

        def body(wb):
            return fn(wb, AXIS, x=stat)
    elif cell.op in MATMUL_OPS:
        w = jnp.ones(shapes["w"], dt)

        def body(x):
            return fn(x, AXIS, w=w)
    else:
        def body(x):
            return fn(x, AXIS)

    sm = shard_map(body, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS),
                   check_vma=False)
    spec = NamedSharding(mesh, P(AXIS))
    rows, width = shapes["x"]
    x = jax.device_put(jnp.ones((p * rows, width), dt), spec)
    return jax.jit(sm).lower(x).compile(), x


def _compiled_hier(cell: OpCell, impl: str):
    """Compile a HIERARCHICAL plain cell's replay: the joint ``p x p2``
    group as a real two-axis host mesh, payload sharded over both axes in
    outer-major order (exactly the dispatch-time layout), the impl called
    with ``inner_axis=`` — so the measured backend replays the same
    composed schedule the api would run."""
    mesh = _mesh2(cell.p, cell.p2)
    fn = C.REGISTRY[cell.op][impl].fn
    shapes = problem_shapes(cell)
    dt = jnp.dtype(cell.dtype if cell.dtype else "float32")

    def body(x):
        return fn(x, AXIS, inner_axis=AXIS2)

    sm = shard_map(body, mesh=mesh, in_specs=P((AXIS, AXIS2)),
                   out_specs=P((AXIS, AXIS2)), check_vma=False)
    spec = NamedSharding(mesh, P((AXIS, AXIS2)))
    rows, width = shapes["x"]
    x = jax.device_put(jnp.ones((cell.world() * rows, width), dt), spec)
    return jax.jit(sm).lower(x).compile(), x


def _compiled_2d(cell: OpCell, impl: str):
    """Compile a 2-D cell's replay on the (outer, inner) host mesh.

    The payload streams over the OUTER axis exactly as at dispatch: the
    forward cell shards the weight's columns over ``AXIS``, the ``2dT``
    cell shards the cotangent's rows; the stationary operand is a
    shard-local closure constant with the recorded per-rank shape."""
    q = max(cell.p2, 1)
    mesh = _mesh2(cell.p, q)
    fn = C.REGISTRY[cell.op][impl].fn
    shapes = problem_shapes(cell)
    dt = jnp.dtype(cell.dtype if cell.dtype else "float32")
    stat = jnp.ones(shapes["w"], dt)
    xpose = cell.mm_role == "2dT"

    def body(payload):
        return fn(payload, AXIS, x=stat, rs_axis=AXIS2, xpose=xpose)

    rows, cols = shapes["x"]
    if xpose:
        in_spec, x = P(AXIS, None), jnp.ones((cell.p * rows, cols), dt)
    else:
        in_spec, x = P(None, AXIS), jnp.ones((rows, cell.p * cols), dt)
    sm = shard_map(body, mesh=mesh, in_specs=in_spec,
                   out_specs=P(AXIS2, None), check_vma=False)
    spec = NamedSharding(mesh, in_spec)
    x = jax.device_put(x, spec)
    return jax.jit(sm).lower(x).compile(), x


@lru_cache(maxsize=8)
def _barrier(world: int):
    """A 1-element psum over the replay world's devices."""
    mesh = _mesh(world)

    def body(x):
        return jax.lax.psum(x, AXIS)

    sm = shard_map(body, mesh=mesh, in_specs=P(AXIS), out_specs=P(),
                   check_vma=False)
    spec = NamedSharding(mesh, P(AXIS))
    x = jax.device_put(jnp.ones((mesh.devices.size,), jnp.float32), spec)
    return jax.jit(sm).lower(x).compile(), x


def sample_latency(cell: OpCell, impl: str, count: int,
                   *, barrier: bool = True) -> list[float]:
    """``count`` barrier-synced wall-clock samples of one cell (s)."""
    fn, x = _compiled(cell, impl)
    bar, bx = _barrier(cell.world())
    # warm one execution so first-run allocation noise is out of the samples
    jax.block_until_ready(fn(x))
    out = []
    for _ in range(count):
        if barrier:
            jax.block_until_ready(bar(bx))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        out.append(time.perf_counter() - t0)
    return out


def sweep_axis(op: str, sizes, *, impl: str = "default",
               count: int = 5) -> list[tuple[int, float]]:
    """Measured ``(payload_bytes, median_seconds)`` points of one op's
    default ring over the host axis — the input ``costmodel.fit_topo`` /
    ``costmodel.MeshTopo.fit`` turn into per-tier alpha/beta/gamma.

    The per-tier Topo parameters a hierarchical cost model prices with
    must come from sweeps like this, not assumed constants: fit the tier
    you can run (``fit_topo(axis_size(), sweep_axis("allgather", ...),
    sweep_axis("allreduce", ...))``) and derive unreachable tiers via the
    published hardware RATIOS (``Topo.scaled``), keeping the fitted
    absolutes."""
    import statistics
    out = []
    for nbytes in sizes:
        cell = host_cell(op, int(nbytes))
        out.append((int(nbytes),
                    statistics.median(sample_latency(cell, impl, count))))
    return out


def make_sampler(cell: OpCell, impl: str):
    """Adapter to the NREP estimator's (msize, count) -> latencies shape.

    The probe size rescales the cell via ``OpCell.scaled_to`` — for fused
    cells the recorded GEMM aspect (K, N and the role) is preserved while
    the payload-tied dim shrinks/grows with the message size.
    """
    def sampler(msize: int, count: int):
        return sample_latency(cell.scaled_to(msize), impl, count)
    return sampler
