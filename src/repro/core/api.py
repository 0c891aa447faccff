"""The dispatching collective API — the PGMPITuneLib "PMPI layer".

All framework code (dist/, models/, train/) calls these entry points instead
of raw ``jax.lax`` collectives.  Selection order per call:

1. explicit ``impl=`` argument              (unit tests, hillclimbing)
2. context ``force`` table                  (PGMPITuneCLI ``--module=op:alg=x``)
3. ``PGTUNE_MODULE`` environment variable   (same syntax as the paper's CLI)
4. phase-specific performance profiles      (trace-replay tuning; the store
   matching the active ``api.phase`` tag)
5. loaded performance profiles              (PGMPITuneD online redirection)
6. the live fleet ``store_ref``             (hot-swappable epochal stores;
   see ``profiles.StoreRef``)
7. the default implementation

Dispatch happens at TRACE time: JAX shapes are static, so the profile's
O(log M) binary search runs while tracing and the compiled program contains
only the winning algorithm — zero runtime overhead (an improvement over the
paper's runtime hash+bsearch, see DESIGN.md §2).

The context also carries the scratch budget (the paper's
``size_msg_buffer_bytes``): a mock-up whose Table-1 extra memory exceeds the
budget is not applied, exactly like PGMPITuneLib refusing replacements when
the user-controlled buffer is too small.

Every dispatch is recorded; ``format_footer()`` emits the paper's Listing-2
``#@pgmpi alg <op> <bytes> <impl>`` trailer.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading

import jax

from repro.core import collectives as C
from repro.core._axis import axis_size
from repro.core.cell import OP_MM_ROLE, OpCell
from repro.core.profiles import OP_TO_MPI, ProfileStore

_TLS = threading.local()


DEFAULT_PHASE = "fwd"


@dataclasses.dataclass(frozen=True)
class DispatchRecord:
    """One dispatched collective: the full problem cell, the impl the
    dispatcher chose, and the workload phase tag.  Destructures as the
    legacy ``(op, p, nbytes, impl, phase)`` 5-tuple."""
    cell: OpCell
    impl: str
    phase: str

    @property
    def op(self) -> str:
        return self.cell.op

    @property
    def p(self) -> int:
        return self.cell.p

    @property
    def nbytes(self) -> int:
        return self.cell.nbytes

    def __iter__(self):
        yield from (self.cell.op, self.cell.p, self.cell.nbytes, self.impl,
                    self.phase)


@dataclasses.dataclass
class TuneContext:
    profiles: ProfileStore | None = None
    force: dict[str, str] = dataclasses.field(default_factory=dict)
    scratch_budget_bytes: int | None = None
    record: list[DispatchRecord] = dataclasses.field(default_factory=list)
    chunk_bytes: int = 0
    phase_profiles: dict[str, ProfileStore] | None = None
    # fleet retuning: the live hot-swappable stores (profiles.StoreRef)
    store_ref: object | None = None
    # per-axis interconnect map (costmodel.MeshTopo): stamps each
    # dispatched cell's tier token so profiles / traces key by tier
    mesh_topo: object | None = None


def _ctx() -> TuneContext | None:
    return getattr(_TLS, "ctx", None)


_GLOBAL_MESH_TOPO = None


def set_mesh_topo(topo) -> None:
    """Install a process-wide ``costmodel.MeshTopo`` describing which
    interconnect tier each mesh axis runs on.  Dispatch stamps every
    cell's ``tier`` token from it (a ``tuned(mesh_topo=...)`` context
    overrides it); ``None`` uninstalls."""
    global _GLOBAL_MESH_TOPO
    _GLOBAL_MESH_TOPO = topo


def current_mesh_topo():
    ctx = _ctx()
    if ctx is not None and ctx.mesh_topo is not None:
        return ctx.mesh_topo
    return _GLOBAL_MESH_TOPO


def current_phase() -> str:
    """The active workload phase tag (see ``phase``); default ``"fwd"``."""
    return getattr(_TLS, "phase", DEFAULT_PHASE)


@contextlib.contextmanager
def phase(name: str):
    """Tag every dispatch issued inside with workload phase ``name``.

    Phases name the coarse callsite classes of an LM step — ``fwd`` (the
    ambient default), ``bwd`` (custom-VJP backwards + grad sync; dist/ops
    and train/trainer set this), ``prefill`` / ``decode`` (serving; set by
    launch/serve).  The tag is captured at TRACE time into
    ``TuneContext.record`` and selects the matching store from
    ``tuned(phase_profiles=...)``.
    """
    prev = current_phase()
    _TLS.phase = name
    try:
        yield
    finally:
        _TLS.phase = prev


@contextlib.contextmanager
def tuned(profiles: ProfileStore | None = None,
          force: dict[str, str] | None = None,
          scratch_budget_bytes: int | None = None,
          chunk_bytes: int = 0,
          phase_profiles: dict[str, ProfileStore] | None = None,
          record: list | None = None,
          store_ref=None,
          mesh_topo=None):
    """Activate tuning for every ``repro.core.api`` collective issued inside.

    ``force`` maps op name -> impl name (the CLI library's static selection);
    ``profiles`` is the PGMPITuneD mode.  ``phase_profiles`` maps a phase
    tag (see ``phase``) to a phase-specific ``ProfileStore`` consulted
    before ``profiles`` — the trace-replay tuner (``tuner.tune_trace``)
    emits these.  ``record`` lets the caller supply the sink dispatches
    are appended to (a list shared across nested builder contexts, or a
    ``trace.ShardRecorder`` sampling across recompilations).  Without any
    of these, defaults are used but calls are still recorded.

    Fleet mode: ``store_ref`` (a ``profiles.StoreRef``) is consulted
    after the explicit stores and read LIVE — swapping a new epoch into
    the ref changes what later jit traces select without rebuilding the
    context.  An executable already compiled keeps the impls it was
    traced with; a new epoch reaches a step at its next trace.
    """
    prev = _ctx()
    ctx = TuneContext(profiles=profiles, force=dict(force or {}),
                      scratch_budget_bytes=scratch_budget_bytes,
                      chunk_bytes=chunk_bytes,
                      phase_profiles=(dict(phase_profiles)
                                      if phase_profiles else None),
                      record=record if record is not None else [],
                      store_ref=store_ref, mesh_topo=mesh_topo)
    _TLS.ctx = ctx
    try:
        yield ctx
    finally:
        _TLS.ctx = prev


def parse_module_spec(spec: str) -> dict[str, str]:
    """Parse the paper's ``--module=allgather:alg=allgather_as_gather_bcast``
    syntax (';'-separated for multiple ops)."""
    out: dict[str, str] = {}
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        op, _, alg = part.partition(":")
        key, _, val = alg.partition("=")
        if key != "alg" or not val:
            raise ValueError(f"bad module spec {part!r}")
        out[op.strip()] = val.strip()
    return out


_ENV_FORCE_CACHE: tuple[str, dict[str, str]] = ("", {})


def _env_force() -> dict[str, str]:
    """Parsed ``PGTUNE_MODULE``, memoized on the raw string — dispatch is a
    trace-time hot path and the env var rarely changes mid-process."""
    global _ENV_FORCE_CACHE
    spec = os.environ.get("PGTUNE_MODULE", "")
    if spec != _ENV_FORCE_CACHE[0]:
        _ENV_FORCE_CACHE = (spec, parse_module_spec(spec) if spec else {})
    return _ENV_FORCE_CACHE[1]


def _payload_bytes(x) -> int:
    return int(x.size) * x.dtype.itemsize


def _make_cell(op: str, payload, axis: str, kw) -> OpCell:
    """The dispatch-time tuning cell: payload + full problem geometry.

    ``payload`` is the operand the collective moves (its bytes are the
    dispatch key); for fused ops the per-callsite GEMM dims are read off
    the actual operands, so profiles/traces/measurement all see the true
    matmul.
    """
    p = axis_size(axis)
    nbytes = _payload_bytes(payload)
    role = OP_MM_ROLE.get(op)
    mt = current_mesh_topo()
    if role is None:
        inner = kw.get("inner_axis")
        if inner is not None:
            # hierarchical plain cell: p = outer (slow) axis, p2 = inner
            tier = mt.tier_token(axis, inner) if mt is not None else ""
            return OpCell(op, p, nbytes, str(payload.dtype),
                          p2=axis_size(inner), tier=tier)
        tier = mt.tier_token(axis) if mt is not None else ""
        return OpCell(op, p, nbytes, str(payload.dtype), tier=tier)
    if role == "2d":
        # two-axis op: p = outer stream axis, p2 = inner reduce-scatter
        # axis; recorded dims are the PER-RANK GEMM (see core/cell.py).
        # The tier token is always (stream axis / rs axis) — the costmodel
        # swaps them itself for the transpose schedule.
        p2 = axis_size(kw["rs_axis"])
        tier = (mt.tier_token(axis, kw["rs_axis"])
                if mt is not None else "")
        if kw.get("xpose"):  # payload g [T/p, M] streamed+contracted
            mm_k, mm_m = p * payload.shape[0], payload.shape[-1]
            mm_n = kw["x"].shape[-1]
            return OpCell(op, p, nbytes, str(payload.dtype),
                          mm_k, mm_m, mm_n, "2dT", p2, tier)
        # payload w [K, M/p] column block streamed over the outer axis
        mm_k, mm_m = payload.shape[0], kw["x"].shape[0]
        mm_n = p * payload.shape[-1]
        return OpCell(op, p, nbytes, str(payload.dtype),
                      mm_k, mm_m, mm_n, "2d", p2, tier)
    tier = mt.tier_token(axis) if mt is not None else ""
    if role == "gather":     # payload x [n, K] gathered over rows, w [K, M]
        mm_k, mm_m = payload.shape[-1], p * payload.shape[0]
        mm_n = kw["w"].shape[-1]
    elif role == "scatter":  # payload x [p*n, K] rows scattered, w [K, M]
        mm_k, mm_m = payload.shape[-1], payload.shape[0]
        mm_n = kw["w"].shape[-1]
    else:                    # contract: payload = streamed w block [K/p, M]
        mm_k, mm_m = p * payload.shape[0], kw["x"].shape[0]
        mm_n = payload.shape[-1]
    return OpCell(op, p, nbytes, str(payload.dtype), mm_k, mm_m, mm_n, role,
                  tier=tier)


def _select(op: str, payload, axis: str, impl: str | None, kw) -> str:
    ctx = _ctx()
    # hot-path short-circuit: with no explicit impl, no force table, no
    # profiles and no phase profiles, the answer is "default" — skip the
    # cell/phase/profile machinery entirely (dispatch runs at trace time
    # but sits on every collective of every jit trace; see
    # benchmarks/bench_dispatch.py for the win).  The pow2 and scratch
    # guards never demote "default", so skipping them is exact.
    if impl is None and (ctx is None or (not ctx.force and ctx.profiles is
                                         None and ctx.phase_profiles is
                                         None and ctx.store_ref is
                                         None)) and not _env_force():
        if ctx is not None:
            ctx.record.append(DispatchRecord(_make_cell(op, payload, axis,
                                                        kw),
                                             "default", current_phase()))
        return "default"
    cell = _make_cell(op, payload, axis, kw)
    p, nbytes = cell.p, cell.nbytes
    ph = current_phase()
    name = impl
    if name is None and ctx is not None and op in ctx.force:
        name = ctx.force[op]
    if name is None:
        env = _env_force()
        if op in env:
            name = env[op]
    if name is None and ctx is not None:
        if ctx.phase_profiles is not None:
            store = ctx.phase_profiles.get(ph)
            if store is not None:
                name = store.lookup_cell(cell)
        if name is None and ctx.profiles is not None:
            name = ctx.profiles.lookup_cell(cell)
        if name is None and ctx.store_ref is not None:
            # the live fleet generation: read through the mutable ref so a
            # hot-swapped epoch is picked up by every later jit trace
            name = ctx.store_ref.lookup(cell, ph)
    if name is None:
        name = "default"
    cand = C.REGISTRY[op].get(name)
    if cand is None:
        raise KeyError(f"unknown impl {name!r} for op {op!r}")
    # pow2 guard + scratch budget (paper's size_msg_buffer_bytes semantics)
    # + demotion ledger (a quantized-wire impl that broke its tolerance)
    # + tier-world guard (a hier mock-up needs a two-axis cell; a flat
    #   mock-up over one axis would silently reduce a hier problem wrong)
    if cand.requires_pow2 and (
            (p & (p - 1)) != 0
            or (cell.p2 and (cell.p2 & (cell.p2 - 1)) != 0)):
        name, cand = "default", C.REGISTRY[op]["default"]
    if name != "default" and getattr(cand, "hier", False) != cell.hier:
        name, cand = "default", C.REGISTRY[op]["default"]
    if name != "default" and C.is_demoted(op, name):
        name, cand = "default", C.REGISTRY[op]["default"]
    if (ctx is not None and ctx.scratch_budget_bytes is not None
            and name != "default"
            and cand.extra_bytes(nbytes, p) > ctx.scratch_budget_bytes):
        name, cand = "default", C.REGISTRY[op]["default"]
    if ctx is not None:
        ctx.record.append(DispatchRecord(cell, name, ph))
    return name


def _dispatch(op: str, payload, axis: str, impl: str | None, /, **kw):
    ctx = _ctx()
    if ctx is not None and ctx.chunk_bytes and "chunk" not in kw:
        itemsize = payload.dtype.itemsize
        kw["chunk"] = max(1, ctx.chunk_bytes // itemsize)
    name = _select(op, payload, axis, impl, kw)
    with jax.named_scope(f"pgtune.{op}.{name}"):
        return C.REGISTRY[op][name].fn(payload, axis, **kw)


# -- public entry points -----------------------------------------------------

def allgather(x, axis: str, *, inner_axis: str | None = None,
              impl: str | None = None):
    """With ``inner_axis`` the gather runs over the joint
    ``(axis, inner_axis)`` group in outer-major block order — ``axis`` is
    the OUTER (slow-tier) axis — and the cell records ``p2`` + the tier
    token, making the hierarchical ``MPIX_*`` mock-ups admissible."""
    if inner_axis is None:
        return _dispatch("allgather", x, axis, impl)
    return _dispatch("allgather", x, axis, impl, inner_axis=inner_axis)


def allreduce(x, axis: str, *, inner_axis: str | None = None,
              impl: str | None = None, **kw):
    """With ``inner_axis`` the sum runs over the joint group (see
    ``allgather``)."""
    if inner_axis is not None:
        kw["inner_axis"] = inner_axis
    return _dispatch("allreduce", x, axis, impl, **kw)


def reducescatter(x, axis: str, *, inner_axis: str | None = None,
                  impl: str | None = None):
    """With ``inner_axis`` the scatter runs over the joint group: rank
    ``(i, j)`` receives joint-sum block ``i*q + j`` (outer-major)."""
    if inner_axis is None:
        return _dispatch("reducescatter", x, axis, impl)
    return _dispatch("reducescatter", x, axis, impl, inner_axis=inner_axis)


def alltoall(x, axis: str, *, impl: str | None = None):
    return _dispatch("alltoall", x, axis, impl)


def bcast(x, axis: str, *, root: int = 0, impl: str | None = None):
    return _dispatch("bcast", x, axis, impl, root=root)


def gather(x, axis: str, *, root: int = 0, impl: str | None = None):
    return _dispatch("gather", x, axis, impl, root=root)


def scatter(x, axis: str, *, root: int = 0, impl: str | None = None):
    return _dispatch("scatter", x, axis, impl, root=root)


def reduce(x, axis: str, *, root: int = 0, impl: str | None = None, **kw):
    return _dispatch("reduce", x, axis, impl, root=root, **kw)


def scan(x, axis: str, *, op: str = "add", impl: str | None = None):
    return _dispatch("scan", x, axis, impl, op=op)


def exscan(x, axis: str, *, op: str = "add", impl: str | None = None):
    return _dispatch("exscan", x, axis, impl, op=op)


def allgather_matmul(x, w, axis: str, *, impl: str | None = None,
                     return_gathered: bool = False):
    """``all_gather(x, rows) @ w`` — fused-vs-unfused is a tuner decision.

    ``x`` per-shard ``[n, K]`` (the dispatch key is its payload, i.e. the
    bytes the collective moves), ``w`` ``[K, M]`` shard-local.  With
    ``return_gathered=True`` also returns ``all_gather(x)`` (the ring
    materializes it for free; custom VJPs reuse it instead of re-gathering).
    """
    return _dispatch("allgather_matmul", x, axis, impl, w=w,
                     return_gathered=return_gathered)


def matmul_reducescatter(x, w, axis: str, *, impl: str | None = None):
    """``reduce_scatter(x @ w, rows)`` — the mirror of ``allgather_matmul``
    (and its backward pairing).  ``x`` per-shard ``[p*n, K]``, ``w``
    ``[K, M]``; partial products are summed over ``axis`` and row-block i
    lands on shard i."""
    return _dispatch("matmul_reducescatter", x, axis, impl, w=w)


def matmul_accumulate(x, w, axis: str, *, impl: str | None = None,
                      return_gathered: bool = False):
    """``x @ all_gather(w, rows)`` — the contraction-dim collective matmul.

    ``w`` per-shard ``[K/p, M]`` (the K-dim FSDP weight shard; its payload
    is the dispatch key — those are the bytes the collective streams), ``x``
    ``[T, K]`` shard-local -> ``[T, M]``.  The gathered dim is CONTRACTED
    away, so neither row-block ring applies; the ``fused_ring`` mock-up
    streams weight blocks around the ring and accumulates partial products.
    ``return_gathered=True`` additionally returns the assembled full weight
    (the ring materializes it for free; custom VJPs reuse it for dx).
    """
    return _dispatch("matmul_accumulate", w, axis, impl, x=x,
                     return_gathered=return_gathered)


def matmul_reducescatter_2d(x, w, rs_axis: str, ag_axis: str, *,
                            impl: str | None = None,
                            return_gathered: bool = False):
    """``reduce_scatter(x @ all_gather(w, cols over ag_axis), rows over
    rs_axis)`` — the weight-stationary 2-D collective matmul.

    ``w`` per-shard ``[K, M/d]`` (the data-axis FSDP column block of a
    row-parallel weight; its payload is the dispatch key — those are the
    bytes the OUTER ring streams), ``x`` ``[T, K]`` shard-local ->
    ``[T/q, M]`` summed over ``rs_axis``.  Fuses BOTH the data-axis weight
    all-gather and the model-axis reduce-scatter around one matmul;
    fused-vs-unfused is a dispatcher decision per 2-D cell
    (``p`` = outer/gather axis, ``p2`` = inner/scatter axis).
    ``return_gathered=True`` additionally returns the assembled full
    weight ``[K, M]`` (the outer ring materializes it for free; the paired
    VJP reuses it for dx).
    """
    return _dispatch("matmul_reducescatter_2d", w, ag_axis, impl, x=x,
                     rs_axis=rs_axis, return_gathered=return_gathered)


def matmul_reducescatter_2d_t(g, x, rs_axis: str, ag_axis: str, *,
                              impl: str | None = None):
    """``reduce_scatter(all_gather(g, rows over ag_axis)ᵀ @ x, rows over
    rs_axis)`` — the TRANSPOSE 2-D schedule (the dw of the paired VJP).

    ``g`` per-shard ``[T/q, M]`` (the cotangent's gather-axis row block —
    the dispatch payload; its gathered dim is CONTRACTED away), ``x``
    ``[T, K]`` shard-local -> ``[M/d, K]`` summed over ``rs_axis``.
    Unlike the forward, the gather axis is the INNER ring here (the outer
    ring is the travelling accumulator over ``rs_axis``) — ``p`` still
    records the gather/stream axis, ``p2`` the scatter axis.  Dispatches
    through the same op as the forward (cells record role ``2dT``), so
    the tuner arbitrates it per cell too.
    """
    return _dispatch("matmul_reducescatter_2d", g, ag_axis, impl, x=x,
                     rs_axis=rs_axis, xpose=True)


def format_footer(ctx: TuneContext) -> str:
    """The paper's Listing-2 footer: which algorithm served each call."""
    lines = []
    seen = set()
    for op, p, nbytes, name, *_phase in ctx.record:
        key = (op, p, nbytes, name)
        if key in seen:
            continue
        seen.add(key)
        mpi = OP_TO_MPI.get(op, op)
        label = "default" if name == "default" else name
        lines.append(f"#@pgmpi alg {mpi} {nbytes} {label}")
    if ctx.scratch_budget_bytes is not None:
        lines.append(
            f"#@pgmpi config size_msg_buffer_bytes {ctx.scratch_budget_bytes}")
    return "\n".join(lines)
