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

Fleet hot-swap is the exception: ``tuned(plan=Plan(), store_ref=ref)``
switches eligible sites to RUNTIME dispatch — the trace emits
``lax.switch`` over every admissible impl and reads the branch index from
a traced plan vector (``plan_input``), so a new profile epoch changes the
vector's CONTENTS, never the compiled program: zero re-jits on swap.

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
    # fleet retuning: live hot-swappable stores (profiles.StoreRef) and
    # the runtime-dispatch plan (api.Plan) — see module docstring
    store_ref: object | None = None
    plan: "Plan | None" = None
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
          plan: "Plan | None" = None,
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
    context.  ``plan`` additionally switches eligible sites to runtime
    dispatch (``lax.switch`` over admissible impls, branch index from the
    ``plan_input`` vector), so a swap takes effect in ALREADY-COMPILED
    steps with zero re-jits.
    """
    prev = _ctx()
    ctx = TuneContext(profiles=profiles, force=dict(force or {}),
                      scratch_budget_bytes=scratch_budget_bytes,
                      chunk_bytes=chunk_bytes,
                      phase_profiles=(dict(phase_profiles)
                                      if phase_profiles else None),
                      record=record if record is not None else [],
                      store_ref=store_ref, plan=plan, mesh_topo=mesh_topo)
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


# ---------------------------------------------------------------------------
# runtime dispatch plans (fleet hot-swap; DESIGN_TRACE.md "epochal hot-swap")
# ---------------------------------------------------------------------------

#: recorded impl marker for sites dispatched through a runtime plan — the
#: branch taken is decided per call by the plan vector, not at trace time
PLAN_IMPL = "plan"


class Plan:
    """A runtime dispatch plan: the fixed-capacity impl-index vector that
    makes profile hot-swaps take effect WITHOUT a re-jit.

    Static dispatch bakes the chosen impl into the jit trace, so a new
    profile epoch would need a re-trace to matter.  Under a Plan, each
    eligible dispatch site instead emits ``lax.switch`` over its full
    admissible impl list and reads the branch index out of a traced int32
    vector the step function feeds in (``plan_input``).  The vector's
    SHAPE is the fixed ``capacity`` — it never changes, so neither does
    the compiled program; its CONTENTS are re-derived from the live
    stores (``vector(ref)``) whenever an epoch lands.

    Sites are keyed ``(cell, phase)``: later recompilations (new shapes,
    donation misses) re-register existing sites onto their stable slots
    and allocate fresh slots for new cells from the spare capacity.  When
    capacity runs out (or an op's admissible set collapses to just the
    default) the site falls back to ordinary static dispatch — graceful,
    and visible via ``len(plan)`` vs ``plan.capacity``.
    """

    def __init__(self, capacity: int = 128):
        self.capacity = int(capacity)
        self._sites: dict[tuple[OpCell, str],
                          tuple[int, tuple[str, ...]]] = {}

    def __len__(self) -> int:
        return len(self._sites)

    def slot(self, cell: OpCell, phase: str,
             impls: tuple[str, ...]) -> int | None:
        """Stable vector slot for a dispatch site (None = dispatch
        statically: capacity exhausted, or the admissible set drifted
        from what this site was registered with)."""
        key = (cell, phase)
        hit = self._sites.get(key)
        if hit is not None:
            s, known = hit
            return s if known == impls else None
        if len(self._sites) >= self.capacity:
            return None
        s = len(self._sites)
        self._sites[key] = (s, impls)
        return s

    def sites(self) -> list[tuple[OpCell, str, tuple[str, ...]]]:
        return [(cell, ph, impls) for (cell, ph), (_s, impls)
                in sorted(self._sites.items(), key=lambda kv: kv[1][0])]

    def _resolve(self, cell, ph, store_ref, base, phases):
        if store_ref is not None:
            return store_ref.lookup(cell, ph)
        store = (phases or {}).get(ph)
        name = store.lookup_cell(cell) if store is not None else None
        if name is None and base is not None:
            name = base.lookup_cell(cell)
        return name

    def vector(self, store_ref=None, *, base: ProfileStore | None = None,
               phases: dict[str, ProfileStore] | None = None):
        """The plan vector for the CURRENT profile generation: slot i
        holds the index (into that site's admissible impl list, 0 =
        default) the live stores select.  Unregistered slots stay 0."""
        import numpy as np
        vec = np.zeros(self.capacity, dtype=np.int32)
        for (cell, ph), (s, impls) in self._sites.items():
            name = self._resolve(cell, ph, store_ref, base, phases)
            if name in impls:
                vec[s] = impls.index(name)
        return vec

    def explore(self, store_ref=None, *, eps: float, rng,
                base: ProfileStore | None = None,
                phases: dict[str, ProfileStore] | None = None):
        """The exploration-budget vector: start from ``vector(...)`` and,
        per site, with probability ``eps`` flip to the runner-up impl —
        the next entry in the site's admissible ring (profiles only store
        winners, so "next" stands in for second-best; for default-serving
        sites that is the first mock-up).  Returns ``(vec, explored)``
        where ``explored`` maps ``(cell, phase) -> impl`` for the flipped
        sites, so the serve loop can attribute the latencies it measures
        (``ShardRecorder.observe``) to what actually ran."""
        vec = self.vector(store_ref, base=base, phases=phases)
        explored: dict[tuple[OpCell, str], str] = {}
        for (cell, ph), (s, impls) in sorted(self._sites.items(),
                                             key=lambda kv: kv[1][0]):
            if len(impls) < 2 or float(rng.random()) >= eps:
                continue
            vec[s] = (int(vec[s]) + 1) % len(impls)
            explored[(cell, ph)] = impls[vec[s]]
        return vec, explored


@contextlib.contextmanager
def plan_input(vec):
    """Expose the enclosing step function's traced plan-vector argument
    to dispatch sites (builders wrap the model call in this; the vector
    itself must be an ARGUMENT of the jitted function — a closed-over
    array would be baked in as a constant and defeat the hot swap)."""
    prev = getattr(_TLS, "plan_vec", None)
    _TLS.plan_vec = vec
    try:
        yield
    finally:
        _TLS.plan_vec = prev


class EpochTripwire:
    """Plan-level auto-rollback: revert a freshly adopted epoch whose
    OBSERVED cost regresses past the prior epoch's.

    The tuner's staleness/digest guards stop bad *publishes*; nothing on
    the read side stops a *well-formed but wrong* epoch — profiles tuned
    from poisoned measurements that make every step slower.  The tripwire
    closes that hole at the one place regression is observable: the serve
    loop's per-step cost.  Feed it each step's observed cost (wall-clock
    delta, or the modeled cost the bench synthesizes) via ``observe``;
    it buckets costs by the ``StoreRef``'s live epoch, takes the median
    of a finished epoch's window as the next epoch's baseline, and when
    the current epoch's windowed median exceeds ``threshold ×`` baseline
    it calls ``ref.rollback()`` — vector contents only, zero re-jit,
    and the bad epoch is poisoned against re-adoption.

    The window is a deque of the last ``window`` costs; medians make a
    single exploration spike or latency outlier unable to trip it (the
    same robustness argument as ``tuner.FeedbackBackend``'s MAD filter).
    """

    def __init__(self, ref, *, threshold: float = 1.5, window: int = 8,
                 min_samples: int = 4):
        self.ref = ref
        self.threshold = float(threshold)
        self.window = int(window)
        self.min_samples = int(min_samples)
        self._epoch = ref.epoch
        self._costs: list[float] = []
        self._baseline: float | None = None   # prior epoch's median cost
        self.fired: list[tuple[int, int]] = []  # (bad epoch, restored)

    @property
    def baseline(self) -> float | None:
        return self._baseline

    def observe(self, cost: float) -> bool:
        """Record one observed step cost under the CURRENT live epoch;
        returns True iff this observation fired a rollback."""
        import statistics
        epoch = self.ref.epoch
        if epoch != self._epoch:
            if epoch > self._epoch and len(self._costs) >= self.min_samples:
                # the finished epoch's steady-state cost becomes the new
                # epoch's yardstick
                self._baseline = statistics.median(self._costs)
            # on epoch < self._epoch (a rollback we didn't fire) the
            # baseline stays: it IS the restored epoch's own median
            self._costs = []
            self._epoch = epoch
        self._costs.append(float(cost))
        del self._costs[:-self.window]
        if self._baseline is None or len(self._costs) < self.min_samples:
            return False
        med = statistics.median(self._costs)
        if med <= self.threshold * self._baseline:
            return False
        restored = self.ref.rollback()
        if restored is None:
            return False   # nothing retained; keep serving + observing
        self.fired.append((epoch, restored))
        self._epoch = restored
        self._costs = []
        return True


def _admissible_impls(op: str, cell: OpCell,
                      ctx: TuneContext) -> tuple[str, ...]:
    """The impls a runtime plan may switch between for one site, in a
    deterministic order (default first) — the same §4.2 admission rules
    static dispatch applies (pow2 guard, Table-1 scratch budget), which
    only depend on the static cell, never on the profile choice."""
    reg = C.REGISTRY[op]
    p, nbytes = cell.p, cell.nbytes
    out = []
    for name in ["default"] + sorted(n for n in reg if n != "default"):
        impl = reg[name]
        if impl.requires_pow2 and (
                (p & (p - 1)) != 0
                or (cell.p2 and (cell.p2 & (cell.p2 - 1)) != 0)):
            continue
        if name != "default" and getattr(impl, "hier", False) != cell.hier:
            continue
        if name != "default" and C.is_demoted(op, name):
            continue
        if (ctx.scratch_budget_bytes is not None and name != "default"
                and impl.extra_bytes(nbytes, p) > ctx.scratch_budget_bytes):
            continue
        out.append(name)
    return tuple(out)


_NO_PLAN = object()


def _dispatch_plan(op: str, payload, axis: str, ctx: TuneContext,
                   plan_vec, kw):
    """Emit the runtime-dispatch form of one site: ``lax.switch`` over
    the admissible impls, branch index read from the plan vector.
    Returns ``_NO_PLAN`` when the site must dispatch statically."""
    cell = _make_cell(op, payload, axis, kw)
    impls = _admissible_impls(op, cell, ctx)
    if len(impls) < 2:
        return _NO_PLAN
    slot = ctx.plan.slot(cell, current_phase(), impls)
    if slot is None:
        return _NO_PLAN
    ctx.record.append(DispatchRecord(cell, PLAN_IMPL, current_phase()))
    import jax.numpy as jnp
    from jax import lax
    from repro.core._axis import axis_is_vmapped, force_full_perm
    idx = jnp.clip(plan_vec[slot], 0, len(impls) - 1)
    reg = C.REGISTRY[op]
    branches = [(lambda f: (lambda _: f(payload, axis, **kw)))(reg[n].fn)
                for n in impls]
    # switch branches trace deferred, past pshift's own partial-perm
    # fallback — vmap-emulated axes must be told to pad proactively
    axes = [a for a in (axis, kw.get("rs_axis"))
            if isinstance(a, str) and axis_is_vmapped(a)]
    with force_full_perm(axes), jax.named_scope(f"pgtune.{op}.{PLAN_IMPL}"):
        return lax.switch(idx, branches, 0)


def _dispatch(op: str, payload, axis: str, impl: str | None, /, **kw):
    ctx = _ctx()
    if ctx is not None and ctx.chunk_bytes and "chunk" not in kw:
        itemsize = payload.dtype.itemsize
        kw["chunk"] = max(1, ctx.chunk_bytes // itemsize)
    if impl is None and ctx is not None and ctx.plan is not None:
        plan_vec = getattr(_TLS, "plan_vec", None)
        if (plan_vec is not None and op not in ctx.force
                and op not in _env_force()):
            out = _dispatch_plan(op, payload, axis, ctx, plan_vec, kw)
            if out is not _NO_PLAN:
                return out
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
