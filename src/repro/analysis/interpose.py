"""Transparent interposition at the XLA/HLO layer.

PGMPITuneLib's pitch is intercepting collectives *without touching user
code*.  The dispatcher (``repro.core.api``) only sees call sites that go
through ``repro.dist`` — but the compiled HLO of ANY jitted function names
every collective XLA emitted, whoever wrote the model.  This module closes
that gap, in two modes:

**report-only** — :func:`tuning_potential` scans a jitted function's
compiled HLO for collective ops (sync and ``-start``/``-done`` async pairs,
including inside ``while``/scan bodies), maps each site to an
:class:`~repro.core.cell.OpCell` (with adjacent-``dot`` detection so an
all-gather feeding a matmul prices as the fused ``allgather_matmul`` cell),
and prices every cell's default against its best mock-up via the cost
model: "this program's collectives vs. their best mock-ups: X.Yx on the
table" — the paper's 'identify the tuning potential of the library' result
lifted to the XLA level.

**rewrite** — :func:`rewrite` re-traces a ``repro.dist``-shaped function
with tuned mock-ups substituted (profiles / force table), matches the
dispatch records against the baseline HLO's collective sites (proof the
interposition touched the sites it claims), runs both compiled programs,
and checks bit-exactness leaf by leaf.

Parser conventions (operand bytes, async pairing, trip counts) are in
``DESIGN_HLO.md``; ``analysis/hlo.py`` owns the text parsing, this module
owns cell mapping and pricing.
"""
from __future__ import annotations

import dataclasses
import re

from repro.analysis.hlo import (CollectiveSite, HloParseError, Instr,
                                _shape_bytes, _shape_dims, collective_sites,
                                module_world, parse_instructions)
from repro.core import costmodel
from repro.core.cell import HLO_TO_OP, OpCell
from repro.core.costmodel import Topo, V5E_ICI
from repro.core.profiles import ProfileStore

__all__ = [
    "SiteCell", "PotentialReport", "RewriteResult", "map_sites",
    "scan_potential", "tuning_potential", "rewrite", "assert_bitexact",
    "compile_zoo_hlo", "HloParseError",
]


# ---------------------------------------------------------------------------
# HLO site -> OpCell mapping (with adjacent-dot / fused-matmul detection)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SiteCell:
    """One HLO collective site resolved to its tuning cell."""
    site: CollectiveSite
    cell: OpCell
    adjacent_dot: str = ""      # dot instruction name, when one is adjacent
    #: True when the adjacency mapped the site onto a FUSED dispatcher op
    #: (allgather_matmul / matmul_reducescatter); an all-reduce fed by a
    #: dot stays a plain cell but keeps ``adjacent_dot`` as the
    #: fused-matmul-candidate marker.
    fused: bool = False


_DIMS_ATTR_RE = re.compile(r"dimensions=\{([\d,]*)\}")
_LHS_C_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_RHS_C_RE = re.compile(r"rhs_contracting_dims=\{([\d,]*)\}")
_LHS_B_RE = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")
_RHS_B_RE = re.compile(r"rhs_batch_dims=\{([\d,]*)\}")


def _ints(rx: re.Pattern, text: str) -> list[int]:
    m = rx.search(text)
    if not m:
        return []
    return [int(x) for x in m.group(1).split(",") if x]


def _prod(xs) -> int:
    n = 1
    for x in xs:
        n *= x
    return n


@dataclasses.dataclass(frozen=True)
class _DotGeom:
    """GEMM geometry of one HLO dot: full logical [mm_m, mm_k] @ [mm_k,
    mm_n] with batch dims folded into mm_m (flops stay 2·k·m·n)."""
    mm_k: int
    mm_m: int
    mm_n: int
    lhs: str
    rhs: str
    lhs_contracting: tuple[int, ...]
    rhs_contracting: tuple[int, ...]


def _dot_geometry(dot: Instr, dims: dict[str, list[int]]) -> _DotGeom | None:
    names = [o for o in re.findall(r"%([\w\.\-]+)", dot.args) if o in dims]
    if len(names) < 2:
        return None
    lhs, rhs = names[0], names[1]
    ld, rd = dims[lhs], dims[rhs]
    lc = _ints(_LHS_C_RE, dot.line)
    rc = _ints(_RHS_C_RE, dot.line)
    lb = _ints(_LHS_B_RE, dot.line)
    mm_k = _prod(ld[i] for i in lc if i < len(ld)) if lc else 1
    batch = _prod(ld[i] for i in lb if i < len(ld)) if lb else 1
    mm_m = max(1, _prod(ld) // max(mm_k * batch, 1)) * batch
    mm_n = max(1, _prod(rd) // max(mm_k * batch, 1))
    return _DotGeom(mm_k, mm_m, mm_n, lhs, rhs, tuple(lc), tuple(rc))


def _map_one(site: CollectiveSite, comp_instrs: list[Instr],
             dims: dict[str, list[int]], sizes: dict[str, int],
             default_p: int) -> SiteCell:
    """Resolve one collective site to its cell (may raise KeyError for a
    collective class with no dispatcher counterpart)."""
    p = site.group_size or default_p or 1
    dot = None
    # async sites hand their value to consumers via the paired -done, so
    # adjacency detection only runs for sync sites (async stays plain).
    if not site.async_role:
        if site.base_op == "all-gather":
            dot = next((i for i in comp_instrs if i.op == "dot"
                        and site.name in i.operands(sizes)), None)
        elif site.base_op in ("reduce-scatter", "all-reduce") \
                and site.operands:
            producer = next((i for i in comp_instrs
                             if i.name == site.operands[0]), None)
            if producer is not None and producer.op == "dot":
                dot = producer

    if dot is not None:
        g = _dot_geometry(dot, dims)
        if g is not None:
            if site.base_op == "all-gather":
                gdims = _ints(_DIMS_ATTR_RE, site.line)
                gdim = gdims[0] if gdims else 0
                if site.name == g.lhs:
                    role = ("contract" if gdim in g.lhs_contracting
                            else "gather")
                    gemm = (g.mm_k, g.mm_m, g.mm_n)
                else:
                    # gathered operand is the rhs: transpose the logical
                    # GEMM so the gathered side plays lhs (flops identical)
                    role = ("contract" if gdim in g.rhs_contracting
                            else "gather")
                    gemm = (g.mm_k, g.mm_n, g.mm_m)
                return SiteCell(
                    site, OpCell.from_hlo(site.base_op, p,
                                          site.operand_bytes, site.dtype,
                                          gemm=gemm, mm_role=role),
                    adjacent_dot=dot.name, fused=True)
            if site.base_op == "reduce-scatter":
                # matmul_reducescatter convention: the payload is the
                # full-row local input x [mm_m, mm_k] — the dot's lhs
                nbytes = sizes.get(g.lhs, site.operand_bytes)
                return SiteCell(
                    site, OpCell.from_hlo(site.base_op, p, nbytes,
                                          site.dtype,
                                          gemm=(g.mm_k, g.mm_m, g.mm_n),
                                          mm_role="scatter"),
                    adjacent_dot=dot.name, fused=True)
            # dot -> all-reduce: the monolithic allreduce the fused ops
            # replace.  No fused dispatcher op takes this exact shape, so
            # it stays a plain cell — but the adjacency is reported as a
            # fused-matmul candidate.
            return SiteCell(
                site, OpCell.from_hlo(site.base_op, p, site.operand_bytes,
                                      site.dtype),
                adjacent_dot=dot.name, fused=False)
    return SiteCell(site, OpCell.from_hlo(site.base_op, p,
                                          site.operand_bytes, site.dtype))


def map_sites(hlo_text: str, *, default_world: int | None = None) \
        -> tuple[list[SiteCell], list[CollectiveSite]]:
    """Map every collective instruction of a compiled module to an
    ``OpCell``.  Returns ``(mapped, unmapped)`` — a nonempty ``unmapped``
    means a collective class this layer cannot express yet, which report
    consumers treat as a hard failure (the whole point is zero drops)."""
    instrs = parse_instructions(hlo_text)
    dims: dict[str, list[int]] = {}
    for i in instrs:
        arrs = _shape_dims(i.type_str)
        dims[i.name] = arrs[0][1] if arrs else []
    sizes = {i.name: _shape_bytes(i.type_str) for i in instrs}
    by_comp: dict[str, list[Instr]] = {}
    for i in instrs:
        by_comp.setdefault(i.computation, []).append(i)
    world = default_world if default_world is not None \
        else module_world(hlo_text)

    mapped: list[SiteCell] = []
    unmapped: list[CollectiveSite] = []
    for site in collective_sites(hlo_text):
        try:
            mapped.append(_map_one(site, by_comp.get(site.computation, []),
                                   dims, sizes, world))
        except KeyError:
            unmapped.append(site)
    return mapped, unmapped


# ---------------------------------------------------------------------------
# report-only mode: the tuning-potential table
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SiteRow:
    """One priced site of the tuning-potential report."""
    sc: SiteCell
    t_default: float            # modeled seconds, one execution
    best_impl: str
    t_best: float
    tuned_impl: str | None      # profile-selected impl (None: no profiles)
    t_tuned: float

    @property
    def speedup(self) -> float:
        return self.t_default / self.t_best if self.t_best > 0 else 1.0


@dataclasses.dataclass
class PotentialReport:
    """The per-model 'collectives vs. best mock-ups' report."""
    label: str
    world: int
    topo: str
    rows: list[SiteRow]
    unmapped: list[CollectiveSite]

    @property
    def ok(self) -> bool:
        """True when every collective instruction mapped to a cell."""
        return not self.unmapped

    def total_default(self) -> float:
        return sum(r.t_default * r.sc.site.mult for r in self.rows)

    def total_best(self) -> float:
        return sum(r.t_best * r.sc.site.mult for r in self.rows)

    def total_tuned(self) -> float:
        return sum(r.t_tuned * r.sc.site.mult for r in self.rows)

    def potential(self) -> float:
        tb = self.total_best()
        return self.total_default() / tb if tb > 0 else 1.0

    def table(self) -> str:
        hdr = (f"{'site':34} {'op':22} {'p':>4} {'bytes':>12} {'x':>5} "
               f"{'default_us':>11} {'best impl':26} {'best_us':>9} "
               f"{'speedup':>8}")
        lines = [f"# {self.label}: world={self.world} topo={self.topo}",
                 hdr, "-" * len(hdr)]
        for r in sorted(self.rows,
                        key=lambda r: -r.t_default * r.sc.site.mult):
            s = r.sc.site
            name = s.name if len(s.name) <= 34 else s.name[:31] + "..."
            star = "*" if r.sc.fused else (
                "+" if r.sc.adjacent_dot else " ")
            lines.append(
                f"{name:34} {r.sc.cell.op + star:22} {r.sc.cell.p:>4} "
                f"{r.sc.cell.nbytes:>12} {s.mult:>5} "
                f"{r.t_default * 1e6:>11.2f} {r.best_impl:26} "
                f"{r.t_best * 1e6:>9.2f} {r.speedup:>7.2f}x")
        lines.append("-" * len(hdr))
        lines.append(
            f"collectives vs. best mock-ups: {self.potential():.2f}x on "
            f"the table ({self.total_default() * 1e6:.1f}us default vs "
            f"{self.total_best() * 1e6:.1f}us best, {len(self.rows)} "
            f"sites)")
        if any(r.tuned_impl is not None for r in self.rows):
            lines.append(
                f"profile-tuned total: {self.total_tuned() * 1e6:.1f}us "
                f"({self.total_default() / max(self.total_tuned(), 1e-30):.2f}x"
                " vs default)")
        if self.unmapped:
            lines.append(f"UNMAPPED ({len(self.unmapped)}):")
            lines += [f"  {s.hlo_op} {s.name} ({s.operand_bytes} B)"
                      for s in self.unmapped]
        return "\n".join(lines)

    def to_json(self) -> dict:
        return {
            "label": self.label, "world": self.world, "topo": self.topo,
            "ok": self.ok,
            "potential": self.potential(),
            "total_default_s": self.total_default(),
            "total_best_s": self.total_best(),
            "total_tuned_s": self.total_tuned(),
            "n_sites": len(self.rows),
            "n_unmapped": len(self.unmapped),
            "unmapped": [s.hlo_op for s in self.unmapped],
            "rows": [{
                "site": r.sc.site.name,
                "computation": r.sc.site.computation,
                "hlo_op": r.sc.site.hlo_op,
                "op": r.sc.cell.op, "p": r.sc.cell.p,
                "nbytes": r.sc.cell.nbytes, "dtype": r.sc.cell.dtype,
                "mult": r.sc.site.mult,
                "fused": r.sc.fused, "adjacent_dot": r.sc.adjacent_dot,
                "mm": [r.sc.cell.mm_k, r.sc.cell.mm_m, r.sc.cell.mm_n],
                "t_default_s": r.t_default,
                "best_impl": r.best_impl, "t_best_s": r.t_best,
                "tuned_impl": r.tuned_impl, "t_tuned_s": r.t_tuned,
                "speedup": r.speedup,
            } for r in self.rows],
        }


def scan_potential(hlo_text: str, *, topo: Topo = V5E_ICI,
                   profiles: ProfileStore | None = None,
                   default_world: int | None = None,
                   chunk_bytes: int = 0, label: str = "") -> PotentialReport:
    """Price every collective site of a compiled module against its best
    mock-up (and, when ``profiles`` is given, against the profile-selected
    impl — what :func:`rewrite` would substitute)."""
    mapped, unmapped = map_sites(hlo_text, default_world=default_world)
    rows = []
    for sc in mapped:
        sw = costmodel.sweep_cell(sc.cell, topo, chunk_bytes=chunk_bytes)
        t_default = sw.get("default", 0.0)
        best = min(sw, key=sw.get)
        tuned_impl = None
        t_tuned = t_default
        if profiles is not None:
            tuned_impl = profiles.lookup_cell(sc.cell) or "default"
            t_tuned = sw.get(tuned_impl, t_default)
        rows.append(SiteRow(sc, t_default, best, sw[best], tuned_impl,
                            t_tuned))
    return PotentialReport(label=label,
                           world=default_world or module_world(hlo_text),
                           topo=topo.name, rows=rows, unmapped=unmapped)


def tuning_potential(fn, *args, topo: Topo = V5E_ICI,
                     profiles: ProfileStore | None = None,
                     chunk_bytes: int = 0, label: str = "") \
        -> PotentialReport:
    """Report-only interposition: compile ``fn(*args)`` (args may be
    ``ShapeDtypeStruct``s), scan the compiled HLO, price every collective.

    ``fn`` may be a plain callable (it is jitted here) or anything with a
    ``.lower`` method (``jax.jit`` wrappers, shard_map'd programs).
    """
    import jax
    jfn = fn if hasattr(fn, "lower") else jax.jit(fn)
    hlo = jfn.lower(*args).compile().as_text()
    return scan_potential(hlo, topo=topo, profiles=profiles,
                          chunk_bytes=chunk_bytes,
                          label=label or getattr(fn, "__name__", "fn"))


# ---------------------------------------------------------------------------
# rewrite mode: re-trace with tuned mock-ups + bit-exactness check
# ---------------------------------------------------------------------------

#: dispatcher op -> the HLO collective class its DEFAULT lowering anchors on
#: (fused ops in default mode lower to their primary collective + dot)
OP_TO_HLO_CLASS = {v: k for k, v in HLO_TO_OP.items()} | {
    "allgather_matmul": "all-gather",
    "matmul_accumulate": "all-gather",
    "matmul_reducescatter": "reduce-scatter",
    "matmul_reducescatter_2d": "all-gather",
}


@dataclasses.dataclass
class RewriteResult:
    """Outcome of one transparent rewrite (see :func:`rewrite`)."""
    baseline_out: object
    tuned_out: object
    matched: list               # (DispatchRecord, CollectiveSite) pairs
    unmatched_records: list     # dispatches with no baseline HLO site
    extra_sites: list           # HLO collectives with no dispatch record
    changed: list               # tuned-trace records with impl != default
    bitexact: bool
    diffs: list                 # human-readable per-leaf mismatch lines

    @property
    def n_rewritten(self) -> int:
        return len(self.changed)


def _match_records_to_sites(records, sites):
    """Greedy (class, p, nbytes) matching of dispatch records onto HLO
    collective sites — the evidence that the dispatcher's sites ARE the
    compiled module's collectives."""
    free = list(sites)
    matched, unmatched = [], []
    for r in records:
        if r.p <= 1:
            continue            # axis size 1: no collective is emitted
        klass = OP_TO_HLO_CLASS.get(r.op)
        hit = next(
            (s for s in free if s.base_op == klass
             and s.group_size in (0, r.p)
             and s.operand_bytes == r.nbytes), None)
        if hit is not None:
            free.remove(hit)
            matched.append((r, hit))
        else:
            unmatched.append(r)
    return matched, unmatched, free


def rewrite(fn, *args, profiles: ProfileStore | None = None,
            force: dict | None = None, phase_profiles: dict | None = None,
            chunk_bytes: int = 0) -> RewriteResult:
    """Re-trace ``fn`` with tuned mock-ups substituted and compare.

    Baseline: trace/compile/run under a default (recording) dispatch
    context and scan the compiled HLO; every dispatch record is matched to
    an HLO collective site.  Tuned: re-trace under
    ``api.tuned(profiles=..., force=...)`` — the dispatcher swaps matched
    ``repro.dist``-shaped sites to their tuned mock-ups at trace time —
    then run the rewritten program on the same inputs and compare leaves
    bit-for-bit.  Args must be concrete arrays (both programs execute).
    """
    import jax
    import numpy as np
    from repro.core import api

    # Each trace must actually re-run the dispatcher: jax caches traces by
    # function identity, so without this the tuned pass silently reuses
    # the baseline jaxpr and no substitution happens.
    jax.clear_caches()
    rec0: list = []
    with api.tuned(record=rec0):
        c0 = jax.jit(fn).lower(*args).compile()
    hlo0 = c0.as_text()
    out0 = c0(*args)

    jax.clear_caches()
    rec1: list = []
    with api.tuned(profiles=profiles, force=force,
                   phase_profiles=phase_profiles, chunk_bytes=chunk_bytes,
                   record=rec1):
        c1 = jax.jit(fn).lower(*args).compile()
    out1 = c1(*args)

    mapped, _un = map_sites(hlo0)
    matched, unmatched, extra = _match_records_to_sites(
        rec0, [sc.site for sc in mapped])
    changed = [r for r in rec1 if r.impl != "default"]

    l0, t0 = jax.tree_util.tree_flatten(out0)
    l1, t1 = jax.tree_util.tree_flatten(out1)
    diffs: list[str] = []
    if t0 != t1:
        diffs.append(f"output trees differ: {t0} vs {t1}")
    else:
        for i, (a, b) in enumerate(zip(l0, l1)):
            a = np.asarray(a)
            b = np.asarray(b)
            if a.shape != b.shape or a.dtype != b.dtype:
                diffs.append(f"leaf {i}: {a.dtype}{a.shape} vs "
                             f"{b.dtype}{b.shape}")
            elif a.tobytes() != b.tobytes():
                fa = a.astype(np.float64) if a.dtype.kind in "fc" else a
                fb = b.astype(np.float64) if b.dtype.kind in "fc" else b
                diffs.append(f"leaf {i}: max |delta| = "
                             f"{np.max(np.abs(fa - fb))}")
    return RewriteResult(out0, out1, matched, unmatched, extra, changed,
                         bitexact=not diffs, diffs=diffs)


def assert_bitexact(res: RewriteResult) -> None:
    if not res.bitexact:
        raise AssertionError(
            "rewritten program is not bit-exact vs baseline:\n  "
            + "\n  ".join(res.diffs))


# ---------------------------------------------------------------------------
# zoo integration: compile one model-zoo program on a host mesh
# ---------------------------------------------------------------------------


def compile_zoo_hlo(arch: str, *, kind: str = "train",
                    mesh_shape: tuple[int, int] = (2, 4),
                    smoke: bool = True, seq_len: int = 32,
                    global_batch: int = 8, n_micro: int = 1) \
        -> tuple[str, dict]:
    """Compiled-HLO text of one ``configs/`` zoo program on a host mesh.

    The host-device analogue of ``launch/dryrun.run_cell``: builds the
    smoke-sized model, shard_maps the train / prefill / decode step over a
    (data, model) mesh of host devices, and returns
    ``(hlo_text, info_dict)``.  The caller must have forced enough host
    devices (``XLA_FLAGS=--xla_force_host_platform_device_count=N``)
    BEFORE jax initializes.
    """
    import dataclasses as _dc

    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.shapes import ShapeCell, dp_axes, input_specs
    from repro.models import lm

    n_dev = mesh_shape[0] * mesh_shape[1]
    if len(jax.devices()) < n_dev:
        raise RuntimeError(
            f"compile_zoo_hlo needs {n_dev} devices, found "
            f"{len(jax.devices())}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_dev} before jax "
            "initializes")
    cfg = get_config(arch)
    if smoke:
        cfg = cfg.smoke()
    mesh = make_host_mesh(mesh_shape, ("data", "model"))
    cell = ShapeCell(f"{kind}_hlo", seq_len, global_batch, kind,
                     n_micro=n_micro)

    with mesh:
        args_sds, in_ps = input_specs(cfg, cell, mesh)
        if kind == "train":
            from repro.train.trainer import make_step_fns
            _, train_fn = make_step_fns(cfg, n_micro=cell.n_micro)
            out_ps = (in_ps[0], in_ps[1],
                      {"loss": P(), "grad_norm": P(), "lr": P()})
            fn = shard_map(train_fn, mesh=mesh, in_specs=in_ps,
                           out_specs=out_ps, check_vma=False)
        elif kind == "prefill":
            def pf(params, batch, caches):
                return lm.prefill(params, cfg, batch, caches)
            out_ps = (P(dp_axes(mesh)), in_ps[2])
            fn = shard_map(pf, mesh=mesh, in_specs=in_ps, out_specs=out_ps,
                           check_vma=False)
        elif kind == "decode":
            def dc(params, token, caches, t):
                return lm.decode_step(params, cfg, token, caches, t)
            out_ps = (in_ps[1], in_ps[2])
            fn = shard_map(dc, mesh=mesh, in_specs=in_ps, out_specs=out_ps,
                           check_vma=False)
        else:
            raise ValueError(f"unknown kind {kind!r}")
        hlo = jax.jit(fn).lower(*args_sds).compile().as_text()
    info = {"arch": arch, "kind": kind, "mesh": "x".join(map(str,
                                                             mesh_shape)),
            "smoke": smoke, "seq_len": seq_len,
            "global_batch": global_batch,
            "config": _dc.asdict(cfg) if hasattr(cfg, "__dataclass_fields__")
            else str(cfg)}
    return hlo, info
