"""Offline tuning pass (paper §4.2): benchmark → detect violations → profile.

Workflow, faithful to the paper's three steps:

1. NREP estimation per (op, msize)   — measured backend only (Alg. 1, Eq. 1).
2. Benchmark default + every mock-up; a *violation* is a mock-up at least
   ``min_win`` (paper: 10%) faster than the default.  Among violating
   mock-ups the fastest is selected; one range per message size is written
   (degenerate [s, s] ranges exactly like Listing 1), then adjacent equal
   selections are coalesced.
3. The resulting ``ProfileStore`` drives ``api.tuned(profiles=...)`` — the
   PGMPITuneD online phase.

Two interchangeable backends:

* ``CostModelBackend``  — α-β-γ model (production scales: p = 16…1024).
* ``MeasuredBackend``   — wall-clock on host devices with barrier + NREP.

The tuner also verifies the other two guideline classes from [6]
(monotony / split-robustness) and reports — but does not repair — those.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Sequence

from repro.core import costmodel, measure, nrep
from repro.core import profiles as profiles_mod
from repro.core.cell import OpCell
from repro.core.collectives import REGISTRY, is_demoted
from repro.core.profiles import Profile, ProfileStore, Range

DEFAULT_SIZES = (1, 8, 32, 64, 100, 512, 1024, 4096, 8192, 32768,
                 100_000, 1_048_576, 16_777_216)


@dataclasses.dataclass(frozen=True)
class Measurement:
    cell: OpCell
    impl: str
    latency: float          # seconds (median for measured backend)
    nrep: int = 1

    @property
    def op(self) -> str:
        return self.cell.op

    @property
    def axis_size(self) -> int:
        return self.cell.p

    @property
    def nbytes(self) -> int:
        return self.cell.nbytes


@dataclasses.dataclass(frozen=True)
class Violation:
    gl_kind: str            # "pattern" | "monotony" | "split_robustness"
    op: str
    axis_size: int
    nbytes: int
    detail: str
    speedup: float          # default / best  (>1 means violation)
    best_impl: str | None = None


@dataclasses.dataclass
class TuneReport:
    measurements: list[Measurement]
    violations: list[Violation]
    profiles: ProfileStore
    notes: list[str] = dataclasses.field(default_factory=list)

    def summary(self) -> str:
        pat = [v for v in self.violations if v.gl_kind == "pattern"]
        lines = [f"measurements: {len(self.measurements)}",
                 f"pattern violations: {len(pat)}",
                 f"other violations: {len(self.violations) - len(pat)}",
                 f"profiles written: {len(self.profiles)}"]
        lines += [f"note: {n}" for n in self.notes]
        return "\n".join(lines)


class CostModelBackend:
    """Latency = analytic model; deterministic, any axis size.

    Backends price ``OpCell``s: a cell with recorded matmul geometry is
    priced from its true flops (``costmodel.latency_cell``); geometry-less
    cells use the canonical table.

    ``topo`` may be a flat ``costmodel.Topo`` or a per-axis
    ``costmodel.MeshTopo``: with a mesh topo, each cell's ``tier`` token
    resolves to its (outer, inner) tier pair, so a DCN-crossing cell and
    an all-ICI cell of the same shape price differently — and the
    hierarchical ``MPIX_*`` mock-ups become finitely priced on
    hierarchical cells.
    """

    name = "costmodel"
    supported_axis_size: int | None = None      # any p

    def __init__(self, topo: "costmodel.Topo | costmodel.MeshTopo", *,
                 chunk_bytes: int = 0):
        self.topo = topo
        self.chunk_bytes = chunk_bytes

    def latency(self, cell: OpCell, impl: str) -> float:
        return costmodel.latency_cell(cell, impl, self.topo,
                                      chunk_bytes=self.chunk_bytes)

    def nrep_for(self, cell: OpCell, impl: str) -> int:
        return 1


class MeasuredBackend:
    """Wall-clock on host devices; NREP via the paper's estimator.

    Measures at one world size: ``axis_size`` devices (the first ones the
    process sees), by default all of them.  A 2x2 host measures its
    ``p = 2`` sub-axis cells with ``axis_size=2``.

    Replays each cell's RECORDED problem — for fused collective-matmul
    cells that is the callsite's actual GEMM ``(dtype, mm_k, mm_m, mm_n)``,
    not a canonical square weight.  Fused cells without geometry (v1
    traces) are unmeasurable (``inf``), which the tuner note-skips.
    """

    name = "measured"

    def __init__(self, *, rse_1byte: float = 0.05, rse_large: float = 0.10,
                 K: int = 5, max_nrep: int = 50,
                 axis_size: int | None = None):
        self.axis_size = axis_size or measure.axis_size()
        if self.axis_size > measure.axis_size():
            raise ValueError(f"axis_size {self.axis_size} > the "
                             f"{measure.axis_size()} devices present")
        self.rse_1byte = rse_1byte
        self.rse_large = rse_large
        self.K = K
        self.max_nrep = max_nrep
        self._one_byte: dict[tuple, nrep.OneByteEstimate] = {}
        self._nrep: dict[tuple, int] = {}

    @property
    def supported_axis_size(self) -> int:
        """Wall clock exists only at the world the backend measures on;
        the trace-replay tuner skips (and notes) every other cell."""
        return self.axis_size

    @staticmethod
    def _measurable(cell: OpCell) -> bool:
        return cell.op not in measure.MATMUL_OPS or cell.fused

    def _ob(self, cell: OpCell, impl: str) -> nrep.OneByteEstimate:
        # for fused cells scaled_to(1) floors at ONE GEMM row/block, so the
        # anchor is the minimal fused problem rather than a literal byte —
        # a conservatively high floor; max_nrep bounds the resulting reps
        key = (cell.scaled_to(1), impl)
        if key not in self._one_byte:
            self._one_byte[key] = nrep.estimate_1byte(
                measure.make_sampler(cell, impl),
                rse_threshold=self.rse_1byte, batch0=5, max_samples=60)
        return self._one_byte[key]

    def nrep_for(self, cell: OpCell, impl: str) -> int:
        # memoized: latency() and the Measurement record both ask, and each
        # estimate costs real barrier-synced timed samples
        if not self._measurable(cell):
            return 1
        key = (cell, impl)
        if key not in self._nrep:
            n = nrep.estimate_nrep(measure.make_sampler(cell, impl),
                                   cell.nbytes, self._ob(cell, impl),
                                   rse_threshold=self.rse_large, K=self.K)
            self._nrep[key] = min(n, self.max_nrep)
        return self._nrep[key]

    def latency(self, cell: OpCell, impl: str) -> float:
        if cell.world() != self.axis_size:
            raise ValueError(
                f"measured backend runs at world={self.axis_size}, "
                f"not {cell.world()} (cell p={cell.p}, p2={cell.p2})")
        if not self._measurable(cell):
            # fused op without recorded geometry: nothing faithful to replay
            return math.inf
        count = self.nrep_for(cell, impl)
        samples = measure.sample_latency(cell, impl, count)
        return statistics.median(samples)


def tune(ops: Sequence[str] | None = None,
         sizes: Sequence[int] = DEFAULT_SIZES,
         axis_size: int = 16,
         backend=None,
         *, min_win: float = 0.10,
         scratch_budget_bytes: int | None = None,
         coalesce: bool = True) -> TuneReport:
    """Run the full offline pass and build profiles.

    ``min_win`` is the paper's "only replace if the mock-up is at least 10%
    faster"; ``scratch_budget_bytes`` enforces Table-1 extra memory.
    """
    ops = list(ops or REGISTRY.keys())
    backend = backend or CostModelBackend(costmodel.V5E_ICI)
    p = axis_size
    ms: list[Measurement] = []
    vios: list[Violation] = []
    notes: list[str] = []
    store = ProfileStore()

    sup = getattr(backend, "supported_axis_size", None)
    if sup is not None and p != sup:
        notes.append(f"axis_size {p} != backend's host axis size {sup}; "
                     "nothing measured (run on a mesh of that size or use "
                     "the cost-model backend)")
        return TuneReport(measurements=ms, violations=vios, profiles=store,
                          notes=notes)

    for op in ops:
        picks: list[tuple[int, str]] = []   # (nbytes, winning impl)
        lat_by_size: dict[int, dict[str, float]] = {}
        for nbytes in sizes:
            lats = _measure_cell(OpCell(op, p, nbytes), backend,
                                 scratch_budget_bytes, ms)
            t_def = lats.get("default")
            if t_def is None:
                # default unmeasurable (inf latency / skipped by the
                # backend): nothing to compare mock-ups against — skip the
                # size rather than crash, and record why.
                notes.append(f"{op} p={p} {nbytes}B: default impl "
                             "unmeasurable; size skipped")
                continue
            lat_by_size[nbytes] = lats
            cands = {k: v for k, v in lats.items() if k != "default"}
            if not cands:
                continue
            best = min(cands, key=cands.get)
            if cands[best] < t_def * (1.0 - min_win):
                gl = REGISTRY[op][best].guideline or "EXT"
                vios.append(Violation(
                    "pattern", op, p, nbytes,
                    f"{gl}: {op} default {t_def:.3e}s > {best} "
                    f"{cands[best]:.3e}s", t_def / cands[best], best))
                picks.append((nbytes, best))

        # monotony: T(n1) <= T(n2) for n1 < n2 (default impl)
        sorted_sizes = sorted(lat_by_size)
        for a, b in zip(sorted_sizes, sorted_sizes[1:]):
            ta, tb = lat_by_size[a]["default"], lat_by_size[b]["default"]
            if ta > tb * (1.0 + min_win):
                vios.append(Violation(
                    "monotony", op, p, b,
                    f"T({a}B)={ta:.3e} > T({b}B)={tb:.3e}", ta / tb))
        # split-robustness: k chunks of n/k not faster than one op on n
        for nbytes in sorted_sizes:
            if nbytes < 8:
                continue
            for k in (2, 4):
                part = nbytes // k
                if part in lat_by_size:
                    t_whole = lat_by_size[nbytes]["default"]
                    t_split = k * lat_by_size[part]["default"]
                    if t_split < t_whole * (1.0 - min_win):
                        vios.append(Violation(
                            "split_robustness", op, p, nbytes,
                            f"{k}x{part}B = {t_split:.3e} < {t_whole:.3e}",
                            t_whole / t_split))

        if picks:
            ranges = [Range(nb, nb, impl) for nb, impl in sorted(picks)]
            if coalesce:
                ranges = _coalesce(ranges)
            store.add(Profile(op=op, axis_size=p, ranges=ranges,
                              meta={"backend": backend.name,
                                    "min_win": min_win}))

    return TuneReport(measurements=ms, violations=vios, profiles=store,
                      notes=notes)


def _measure_cell(cell: OpCell, backend,
                  scratch_budget_bytes: int | None,
                  ms: list[Measurement]) -> dict[str, float]:
    """Benchmark every admissible impl of one tuning cell — the §4.2
    admission rules (pow2 guard, Table-1 scratch budget, inf filter)
    shared by the sweep tuner and the trace-replay tuner.  Appends to
    ``ms`` and returns ``{impl: latency}``."""
    lats: dict[str, float] = {}
    p, nbytes = cell.p, cell.nbytes
    for impl_name, impl in REGISTRY[cell.op].items():
        if impl.requires_pow2 and (
                (p & (p - 1)) != 0
                or (cell.p2 and (cell.p2 & (cell.p2 - 1)) != 0)):
            continue
        # hier impls only fit hierarchical cells and vice versa; the cost
        # model prices the mismatch inf, but the measured backend would
        # CRASH replaying a flat mock-up on a two-axis problem — gate here
        if getattr(impl, "hier", False) != cell.hier and impl_name != "default":
            continue
        if impl_name != "default" and is_demoted(cell.op, impl_name):
            continue
        if (scratch_budget_bytes is not None
                and impl_name != "default"
                and impl.extra_bytes(nbytes, p) > scratch_budget_bytes):
            continue
        t = backend.latency(cell, impl_name)
        if math.isinf(t):
            continue
        lats[impl_name] = t
        ms.append(Measurement(cell, impl_name, t,
                              backend.nrep_for(cell, impl_name)))
    return lats


# ---------------------------------------------------------------------------
# trace replay (PGMPI-style per-callsite tuning, arXiv:1606.00215)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TraceTuneReport:
    """Result of tuning against a recorded workload trace.

    ``phase_profiles`` maps each phase tag found in the trace to a
    ``ProfileStore`` built from the (op, axis_size, nbytes) cells that phase
    actually issued — feed it to ``api.tuned(phase_profiles=...)``.
    ``est_default_s`` / ``est_tuned_s`` are the backend's frequency-weighted
    total collective latency per phase (each cell weighted by its trace
    count), i.e. the modeled communication time of replaying the trace with
    defaults vs with the emitted profiles.
    """
    phase_profiles: dict[str, ProfileStore]
    measurements: list[Measurement]
    est_default_s: dict[str, float]
    est_tuned_s: dict[str, float]
    notes: list[str] = dataclasses.field(default_factory=list)

    def store(self, phase: str) -> ProfileStore | None:
        return self.phase_profiles.get(phase)

    def summary(self) -> str:
        lines = []
        for ph in sorted(self.est_default_s):
            d, t = self.est_default_s[ph], self.est_tuned_s[ph]
            n = len(self.phase_profiles.get(ph, ()))
            sp = d / t if t > 0 else 1.0
            lines.append(f"{ph}: {n} profiles, modeled {d*1e6:.1f}us -> "
                         f"{t*1e6:.1f}us ({sp:.2f}x)")
        lines += [f"note: {n}" for n in self.notes]
        return "\n".join(lines) or "empty trace"

    def save(self, directory, *, fmt: str = "text",
             epoch: int | None = None,
             source_digest: str | None = None) -> None:
        """One subdirectory per phase (``<dir>/<phase>/<op>_p<P>.pgtune``) —
        the layout ``profiles.load_stores`` / ``PGTUNE_PROFILE_DIR``
        consumers read back.

        With ``epoch=`` the write becomes a fleet profile *generation*: a
        top-level ``MANIFEST.json`` (epoch, source-shard digest, geometry
        census) is written LAST, so a ``resolve_stores(watch=True)`` ref
        polling the directory only ever swaps in a complete epoch."""
        import pathlib
        d = pathlib.Path(directory)
        for ph, store in sorted(self.phase_profiles.items()):
            store.save(d / ph, fmt=fmt)
        if epoch is not None:
            profiles_mod.write_manifest(d, epoch, source_digest=source_digest,
                                        phases=self.phase_profiles)


def tune_trace(trace, backend=None, *, min_win: float = 0.10,
               scratch_budget_bytes: int | None = None,
               coalesce: bool = True) -> TraceTuneReport:
    """Tune against a recorded op mix instead of a synthetic size sweep.

    For every phase in ``trace`` and every (op, axis_size, nbytes) cell that
    phase recorded, benchmark the default and every admissible mock-up on
    ``backend`` and select the fastest mock-up that beats the default by at
    least ``min_win`` — exactly the §4.2 violation rule, but evaluated only
    at the message sizes / axis sizes the workload actually issued and
    weighted by how often it issued them.  Emits one ``ProfileStore`` per
    phase, so e.g. the backward's reduce-scatters can select a different
    mock-up than the forward's all-gathers.

    With a ``MeasuredBackend`` this is the ROADMAP "measured-backend trace
    replay": each recorded cell is re-executed on the host devices with its
    RECORDED problem — fused collective-matmul cells replay the callsite's
    actual GEMM ``(dtype, mm_k, mm_m, mm_n)`` — and timed (serving profiles
    from wall clock, not the model).  Cells whose world differs from the
    backend's ``supported_axis_size`` are not replayed and are skipped
    with a note; so are fused cells without recorded geometry (v1 traces).

    Emitted profiles are keyed like the cells: fused cells produce one
    geometry profile per ``(op, p, Geom)`` — the store's nearest-cell
    fallback covers unseen shapes at dispatch.
    """
    backend = backend or CostModelBackend(costmodel.V5E_ICI)
    sup = getattr(backend, "supported_axis_size", None)
    ms: list[Measurement] = []
    notes: list[str] = []
    phase_profiles: dict[str, ProfileStore] = {}
    est_default: dict[str, float] = {}
    est_tuned: dict[str, float] = {}
    # fwd and bwd often share cells; measure each OpCell once — this
    # matters for the measured backend doing real timed runs
    lat_cache: dict[OpCell, dict[str, float]] = {}

    for ph in trace.phases():
        picks: dict[tuple, list[tuple[int, str]]] = {}
        # weighted latency per impl of each profile range: cells that one
        # range cannot tell apart (one size in two dtypes) decide together
        groups: dict[tuple, dict[str, float]] = {}
        t_d = t_t = 0.0
        for cell, weight in sorted(trace.cells(phase=ph).items()):
            op, p, nbytes = cell.op, cell.p, cell.nbytes
            if op not in REGISTRY:
                notes.append(f"{ph}: unknown op {op!r}; cell skipped")
                continue
            if sup is not None and cell.world() != sup:
                wd = (f"world={cell.world()} (p={p}, p2={cell.p2})"
                      if cell.p2 else f"p={p}")
                notes.append(f"{ph}: {op} {nbytes}B: {wd} != host axis "
                             f"size {sup}; cell skipped")
                continue
            if cell not in lat_cache:
                lat_cache[cell] = _measure_cell(cell, backend,
                                                scratch_budget_bytes, ms)
            lats = lat_cache[cell]
            t_def = lats.get("default")
            if t_def is None:
                # don't let a fused cell's inf latency vanish silently: say
                # WHY it was unmeasurable — a fused op without recorded
                # geometry (v1 trace) has nothing faithful to replay, and
                # the report footer must carry that (regression:
                # measure.sample_latency inf inside tune_trace aggregation)
                if op in measure.MATMUL_OPS and not cell.fused:
                    notes.append(
                        f"{ph}: {op} p={p} {nbytes}B: fused cell has no "
                        "recorded GEMM geometry (v1 trace?); unmeasurable, "
                        "cell skipped — re-record the trace with schema v2")
                else:
                    notes.append(f"{ph}: {op} p={p} {nbytes}B: default impl "
                                 "unmeasurable; cell skipped")
                continue
            t_d += weight * t_def
            key = (op, p, cell.geom(), cell.profile_tier(), nbytes)
            tot = {k: weight * v for k, v in lats.items()}
            if key in groups:
                tot = {k: v + groups[key][k] for k, v in tot.items()
                       if k in groups[key]}
            groups[key] = tot

        for (op, p, geom, tier, nbytes), tot in groups.items():
            t_def = tot["default"]
            cands = {k: v for k, v in tot.items() if k != "default"}
            best = min(cands, key=cands.get) if cands else None
            if best is not None and cands[best] < t_def * (1.0 - min_win):
                picks.setdefault((op, p, geom, tier), []).append(
                    (nbytes, best))
                t_t += cands[best]
            else:
                t_t += t_def

        for (op, p, geom, tier), pk in sorted(
                picks.items(), key=lambda kv: (kv[0][0], kv[0][1],
                                               str(kv[0][2]), kv[0][3])):
            ranges = [Range(nb, nb, impl) for nb, impl in sorted(pk)]
            if coalesce:
                ranges = _coalesce(ranges)
            meta = {"backend": backend.name, "min_win": min_win,
                    "phase": ph, "source": "trace"}
            phase_profiles.setdefault(ph, ProfileStore()).add(
                Profile(op=op, axis_size=p, ranges=ranges, meta=meta,
                        geom=geom, tier=tier))
        est_default[ph] = t_d
        est_tuned[ph] = t_t

    return TraceTuneReport(phase_profiles=phase_profiles, measurements=ms,
                           est_default_s=est_default, est_tuned_s=est_tuned,
                           notes=notes)


# ---------------------------------------------------------------------------
# fleet feedback (exploration-budget measurements -> next epoch's tuner)
# ---------------------------------------------------------------------------


def _mad_filter(samples: list[float], k: float) -> list[float]:
    """Median/MAD outlier rejection: keep samples within ``k`` robust
    deviations of the median.  The scale is floored at 5% of |median|
    (and an absolute epsilon) because the MAD of near-identical samples
    is 0, which would reject every sample but the exact median.  Returns
    at least ``[median]`` so a cell never loses ALL its observations."""
    if len(samples) < 3 or k <= 0:
        return list(samples)
    med = statistics.median(samples)
    mad = statistics.median([abs(x - med) for x in samples])
    scale = max(mad, 0.05 * abs(med), 1e-12)
    kept = [x for x in samples if abs(x - med) <= k * scale]
    return kept or [med]


class FeedbackBackend:
    """A backend that prefers LIVE fleet measurements over its base estimate.

    The exploration budget (``Plan.explore`` + ``ShardRecorder.observe``)
    deposits real ``(cell, impl, latency)`` samples into the trace shards;
    ``trace.load_shard_latencies`` collects them across the fleet.  Wrapping
    the next epoch's tuner backend in this class makes ``tune_trace`` price
    any (cell, impl) with enough observed samples from the fleet's own wall
    clock — the loop that lets profiles track hardware/load drift — while
    everything unexplored still falls back to the base backend.

    Fleet measurements are HOSTILE inputs: one explored step that landed
    on a network hiccup can be 100× the true latency, and with only a
    handful of samples per (cell, impl) even a median shifts.  Samples
    are therefore filtered at construction with median/MAD outlier
    rejection (drop anything more than ``mad_k`` robust deviations from
    the median; the MAD is floored at 5% of the median so near-identical
    samples don't reject everything); ``rejected`` counts the dropped
    samples for the chaos gates.  Set ``mad_k=0`` to disable.
    """

    def __init__(self, base, observed: dict[tuple[OpCell, str],
                                            Sequence[float]],
                 *, min_samples: int = 3, mad_k: float = 4.0):
        self.base = base
        self.name = f"feedback+{base.name}"
        self.min_samples = min_samples
        self.mad_k = float(mad_k)
        self.rejected = 0
        self._obs: dict[tuple[OpCell, str], list[float]] = {}
        for k, v in observed.items():
            if len(v) == 0:
                continue
            kept = _mad_filter([float(x) for x in v], self.mad_k)
            self.rejected += len(v) - len(kept)
            self._obs[k] = kept

    @property
    def supported_axis_size(self) -> int | None:
        # cells WITH observations need no replay, but unexplored cells
        # still hit the base backend, so its replay constraint stands
        return getattr(self.base, "supported_axis_size", None)

    def observed_for(self, cell: OpCell, impl: str) -> list[float]:
        return list(self._obs.get((cell, impl), ()))

    def latency(self, cell: OpCell, impl: str) -> float:
        s = self._obs.get((cell, impl))
        if s is not None and len(s) >= self.min_samples:
            return statistics.median(s)
        return self.base.latency(cell, impl)

    def nrep_for(self, cell: OpCell, impl: str) -> int:
        s = self._obs.get((cell, impl))
        if s is not None and len(s) >= self.min_samples:
            return len(s)
        return self.base.nrep_for(cell, impl)


def estimate_trace_cost(trace, backend=None, *,
                        base: ProfileStore | None = None,
                        phases: dict[str, ProfileStore] | None = None,
                        scratch_budget_bytes: int | None = None
                        ) -> dict[str, float]:
    """Frequency-weighted modeled collective time of serving ``trace``
    under a given set of profiles — the fleet benchmark's yardstick for
    "the merged profile beats any single-shard profile on the union
    workload".

    For every recorded cell the impl the stores would dispatch (phase
    store, then ``base``, then the default) is priced on ``backend`` and
    weighted by the cell's trace count.  Inadmissible or unmeasurable
    selections fall back to the default impl, mirroring dispatch.
    """
    backend = backend or CostModelBackend(costmodel.V5E_ICI)
    out: dict[str, float] = {}
    for ph in trace.phases():
        total = 0.0
        for cell, weight in sorted(trace.cells(phase=ph).items()):
            if cell.op not in REGISTRY:
                continue
            name = None
            store = (phases or {}).get(ph)
            if store is not None:
                name = store.lookup_cell(cell)
            if name is None and base is not None:
                name = base.lookup_cell(cell)
            if name is None or name not in REGISTRY[cell.op]:
                name = "default"
            impl = REGISTRY[cell.op][name]
            p, nbytes = cell.p, cell.nbytes
            if name != "default" and (
                    (impl.requires_pow2 and (
                        (p & (p - 1)) != 0
                        or (cell.p2 and (cell.p2 & (cell.p2 - 1)) != 0)))
                    or getattr(impl, "hier", False) != cell.hier
                    or is_demoted(cell.op, name)
                    or (scratch_budget_bytes is not None
                        and impl.extra_bytes(nbytes, p)
                        > scratch_budget_bytes)):
                name = "default"
            t = backend.latency(cell, name)
            if math.isinf(t) and name != "default":
                t = backend.latency(cell, "default")
            if math.isinf(t):
                continue
            total += weight * t
        out[ph] = total
    return out


def _coalesce(ranges: list[Range]) -> list[Range]:
    """Merge adjacent measured sizes that picked the same impl into one
    closed range (covers the gap between the discrete sizes)."""
    out: list[Range] = []
    for r in ranges:
        if out and out[-1].impl == r.impl:
            out[-1] = Range(out[-1].lo, r.hi, r.impl)
        else:
            out.append(r)
    return out
