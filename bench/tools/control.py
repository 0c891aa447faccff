#!/usr/bin/env python3
"""Readings that set a cell's limits: the control and the planted faults.

    python3 bench/tools/control.py --workload <name> --seeds 1 2 3 [--seconds S]

Train cells: for each seed the reference's three steps in float32 (the
truth), the same in float8 (e4m3, per-tensor scale, on both operands of
every matrix product: the control) and on half of each batch (a fault),
each compared with the truth as a run of the program would be.  A state
left unchanged reads 1 on ``change_gap`` by construction and needs no run.

Grid cells: for each seed a run of the grid with every payload on a
bfloat16 wire (the control), set-up, a short window at the cell's own
load, and the check.

One JSON line per seed and reading; nothing here runs in the benchmark's
own runs.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def fp8(a):
    import jax.numpy as jnp
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def train_readings(spec, devices, seed):
    from bench.kinds import train
    cell = train.Cell(spec.config, spec.traffic, spec.limits, seed=seed,
                      devices=devices)
    cell.layout(spec.traffic["mesh"][1])
    truth = cell.reference_run()
    return {"control_fp8": train.gaps(cell.reference_run(cast=fp8), truth),
            "fault_half_batch": train.gaps(cell.reference_run(keep=train.half_batch),
                                           truth)}


def grid_readings(spec, devices, seed, seconds):
    from bench.kinds import grid
    cell = grid.Cell(spec.config, spec.traffic, spec.limits, seed=seed,
                     devices=devices, wire_dtype="bfloat16")
    cell.setup()
    cell.window(seconds)
    cell.release()
    return {"control_bf16": {n: v for n, v, _ in cell.check()}}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    spec = harness.cell_spec(harness.load_json(ROOT / "BENCHMARK.json"),
                             args.workload)
    devices = harness.require_chips(spec.chips)
    harness.enable_compile_cache()
    for seed in args.seeds:
        t0 = time.perf_counter()
        if spec.config["kind"] == "train":
            out = train_readings(spec, devices, seed)
        else:
            out = grid_readings(spec, devices, seed, args.seconds)
        for what, numbers in out.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": what, **numbers,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
