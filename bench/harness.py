"""One run of one cell: set-up, measured window, check, result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``<config file>``            the configuration's sizes; its ``kind`` names
                               the driver ``bench/kinds/<kind>.py``
* ``bench/traffic/<traffic>.json``  the traffic mix the driver generates
* ``bench/limits/<workload>.json``  the limits of the cell's check
* ``bench/metrics/<metric>.py``     one per-layer metric's reader

A driver module defines ``Cell(config, traffic, limits, seed, devices)``
with ``setup()``, ``window(seconds) -> {metric: value}``,
``traced_window(seconds) -> info``, ``op_label(hlo_name)``, ``release()``
and ``check() -> [(name, value, limit)]``, plus ``attempted``/``failed``.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import pathlib
import sys
import time

from bench import trace as trace_mod

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class CellSpec:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def cell_spec(bm: dict, name: str) -> CellSpec:
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bm["configs"]}[w["config"]]
    e2e = [m for m in bm["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return CellSpec(name=name, chips=w["chips"],
                    config=load_json(ROOT / cfg["file"]),
                    traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                    limits=load_json(BENCH / "limits" / f"{name}.json"),
                    end_to_end=e2e, per_layer=per_layer)


def require_chips(n: int):
    """The first ``n`` TPU chips; exits (no result) when there are fewer
    or the backend is not a TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < n:
        raise SystemExit(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or
    ``$JAX_COMPILATION_CACHE_DIR``), every program cached."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class ReadContext:
    """What a per-layer reader sees: the trace of the window, what the
    driver reported about it, the driver itself, and the chip's peaks."""
    trace: trace_mod.Trace
    info: dict
    cell: object
    peaks: dict | None


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(spec: CellSpec, devices, *, seed: int, seconds: float,
             trace: bool, t_start: float) -> dict:
    enable_compile_cache()
    driver = importlib.import_module(f"bench.kinds.{spec.config['kind']}")
    cell = driver.Cell(spec.config, spec.traffic, spec.limits, seed=seed,
                       devices=devices)
    t_cell = time.perf_counter()
    cell.setup()
    setup_s = time.perf_counter() - t_start
    phases = {"start": t_cell - t_start, **getattr(cell, "setup_phases", {})}
    print("setup: " + ", ".join(f"{k} {v:.3f}s" for k, v in phases.items()),
          file=sys.stderr)
    moved = [r for r in cell.record if r.impl != "default"]
    print(f"dispatch: {len(cell.record)} sites traced, {len(moved)} not "
          f"default" + "".join(f", {r.op}={r.impl}" for r in moved[:8]),
          file=sys.stderr)
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices)}
    metrics: dict = {}
    breakdown = None
    if trace:
        info, tr = trace_mod.capture(lambda: cell.traced_window(seconds))
        ctx = ReadContext(trace=tr, info=info, cell=cell,
                          peaks=peaks(kind) if tr.ops else None)
        for m in spec.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.mean_busy_s()
        device["window_s"] = tr.window_s
        if tr.ops:
            top = sorted(tr.op_seconds(cell.op_label).items(),
                         key=lambda kv: -kv[1])[:10]
            breakdown = {"device_ops": [[k, v] for k, v in top],
                         "idle_gaps": tr.idle_gaps(tr.chips[0])}
    else:
        values = cell.window(seconds)
        values["setup_s"] = setup_s
        for m in spec.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    device["memory_peak_bytes"] = memory_peak_bytes(devices)
    cell.release()
    check = [(n, float(v), float(lim)) for n, v, lim in cell.check()]
    ok = all(math.isfinite(v) and v <= lim for _, v, lim in check)
    result = {"correct": bool(ok and cell.failed == 0),
              "attempted": int(cell.attempted), "failed": int(cell.failed),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {n: {"value": v, "limit": lim} for n, v, lim in check}
    return result


def emit(result: dict) -> None:
    for name, c in result["check"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv, *, t_start: float) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = cell_spec(load_json(ROOT / "BENCHMARK.json"), args.workload)
    devices = require_chips(spec.chips)
    emit(run_cell(spec, devices, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), t_start=t_start))
    return 0
