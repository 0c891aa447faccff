"""From a profiler trace to what the per-layer readers need.

``capture`` runs a callable under ``jax.profiler`` and parses the
``.xplane.pb`` it writes.  On a TPU each chip is a plane
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed HLO
instruction, named by the instruction's text (``%fusion.8 = f32[...]
fusion(...)``), and ``XLA Modules`` one event per program execution.  A
``while`` instruction appears as an event that spans its body's events:
such containers are dropped, so that busy time and per-op time count each
executed instruction once.  Host spans (``TraceAnnotation``) come from the
``python`` line of ``/host:CPU``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
import time

_DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
_HLO_NAME = re.compile(r"^%?([^\s=]+)")


def hlo_name(event_name: str) -> str:
    """``'%fusion.8 = f32[..] fusion(..)'`` -> ``'fusion.8'``."""
    m = _HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


@dataclasses.dataclass
class Trace:
    """Device ops (leaf instructions only) and host spans of one window.

    Times are in nanoseconds on the trace's clock; ``window_s`` is the
    host-clock length of the traced window."""
    ops: dict[int, list[tuple[float, float, str]]]       # chip -> (start, dur, hlo name)
    host: list[tuple[float, float, str]]                  # python-line spans
    window_s: float

    @property
    def chips(self) -> list[int]:
        return sorted(self.ops)

    def busy_s(self, chip: int) -> float:
        """Seconds in which some instruction ran on ``chip``."""
        return sum(e - s for s, e in merged(self.ops[chip])) * 1e-9

    def mean_busy_s(self) -> float:
        if not self.ops:
            return 0.0
        return sum(self.busy_s(c) for c in self.chips) / len(self.ops)

    def op_seconds(self, label) -> dict[str, float]:
        """Device seconds per ``label(hlo_name)``, averaged over chips;
        ops whose label is None are left out."""
        out: dict[str, float] = {}
        for c in self.chips:
            for _, dur, name in self.ops[c]:
                key = label(name)
                if key is not None:
                    out[key] = out.get(key, 0.0) + dur * 1e-9
        n = max(len(self.ops), 1)
        return {k: v / n for k, v in out.items()}

    def idle_gaps(self, chip: int, top: int = 10) -> list[list]:
        """The longest gaps between busy intervals of ``chip``, each named
        by the innermost host span that covers its middle."""
        iv = merged(self.ops[chip])
        gaps = [(iv[i + 1][0] - iv[i][1], iv[i][1], iv[i + 1][0])
                for i in range(len(iv) - 1)]
        gaps.sort(reverse=True)
        out = []
        for length, s, e in gaps[:top]:
            mid = (s + e) / 2
            cover = [h for h in self.host if h[0] <= mid <= h[0] + h[1]]
            name = min(cover, key=lambda h: h[1])[2] if cover else "none"
            out.append([f"host:{name}", length * 1e-9])
        return out


def merged(ops) -> list[tuple[float, float]]:
    """Union of ``(start, dur, ...)`` intervals as sorted ``(start, end)``."""
    out: list[list[float]] = []
    for s, d, *_ in sorted(ops):
        e = s + d
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def leaf_ops(events) -> list[tuple[float, float, str]]:
    """Drop container events (``while``, ``call``): an event inside whose
    span the next event starts.  Events of one chip's core run one at a
    time, so any overlap is nesting."""
    ev = sorted(events, key=lambda t: (t[0], -t[1]))
    out = []
    for i, (s, d, n) in enumerate(ev):
        if i + 1 < len(ev) and ev[i + 1][0] < s + d:
            continue
        out.append((s, d, n))
    return out


def parse(path: str, window_s: float) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: dict[int, list] = {}
    host: list = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip] = leaf_ops([(e.start_ns, e.duration_ns,
                                           hlo_name(e.name))
                                          for e in line.events])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name == "python":
                    host += [(e.start_ns, e.duration_ns, e.name)
                             for e in line.events]
    return Trace(ops=ops, host=host, window_s=window_s)


def capture(fn):
    """Run ``fn`` (which must block until its device work is done) under
    the profiler; returns ``(fn's result, Trace)``.  The trace is written
    under ``$TMPDIR`` and removed once read."""
    import jax
    d = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(d)
        try:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.window"):
                info = fn()
            window_s = time.perf_counter() - t0
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        return info, parse(max(paths, key=os.path.getmtime), window_s)
    finally:
        shutil.rmtree(d, ignore_errors=True)


_META = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+)\s*=.*?metadata=\{[^}]*?'
                   r'op_name="([^"]*)"')


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+)\s*=')


def hlo_op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` metadata (JAX's name stack) of a
    compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _META.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def hlo_schedule(hlo_text: str) -> list[list[tuple[str, str | None]]]:
    """Each computation's instructions in the order the compiled module
    lists (schedules) them, as ``(name, op_name or None)``: the compiler
    drops the metadata of some instructions it makes (a reduce-scatter
    rewritten as an all-reduce), and their place in the schedule is then
    what tells whose they are."""
    comps, cur = [], None
    for line in hlo_text.splitlines():
        if line.rstrip().endswith("{") and "=" not in line.split("(")[0]:
            cur = []
            comps.append(cur)
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            meta = _META.match(line)
            cur.append((m.group(1), meta.group(2) if meta else None))
    return comps
