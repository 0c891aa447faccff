#!/usr/bin/env python3
"""Several runs of one cell, one process each, one after another.

    python3 bench/tools/series.py --workload <name> --seeds 11 12 13 \
        [--trace 0 0 1] [--seconds S] [--out results/bench_series.jsonl]

Each run is ``bench/run.py`` as the benchmark's command runs it.  This
process never imports JAX, so each child has the chips to itself.  For
each run one JSON line goes to ``--out``: the seed, the trace flag, the
exit code, the wall time, the result line and the check lines.  At the end
the spread of each metric over the runs is printed: the median and the
distance between the quartiles as a share of it
(``statistics.quantiles(values, n=4)``).
"""
import argparse
import json
import pathlib
import statistics
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--trace", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default="results/bench_series.jsonl")
    args = ap.parse_args(argv)
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = args.seconds or bm["run_seconds"]
    traces = args.trace or [0] * len(args.seeds)
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    values: dict[str, list] = {}
    with open(out, "a") as f:
        for seed, tr in zip(args.seeds, traces):
            cmd = [sys.executable, "bench/run.py", "--workload", args.workload,
                   "--seed", str(seed), "--seconds", str(seconds),
                   "--trace", str(tr)]
            t0 = time.perf_counter()
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
            wall = time.perf_counter() - t0
            lines = p.stdout.strip().splitlines()
            try:
                result = json.loads(lines[-1])
            except (IndexError, json.JSONDecodeError):
                result = None
            rec = {"workload": args.workload, "seed": seed, "trace": tr,
                   "rc": p.returncode, "wall_s": wall, "result": result,
                   "stderr_tail": p.stderr[-3000:]}
            f.write(json.dumps(rec) + "\n")
            f.flush()
            if result is None:
                print(f"seed {seed} trace {tr}: rc {p.returncode}, no result;"
                      f" stderr: {p.stderr[-2000:]}", flush=True)
                continue
            m = {k: v["value"] for k, v in result["metrics"].items()}
            print(f"seed {seed} trace {tr} rc {p.returncode} wall {wall:.1f}s "
                  f"correct {result['correct']} metrics {m} "
                  f"check {result['check']} device {result['device']}",
                  flush=True)
            if not tr:
                for k, v in m.items():
                    values.setdefault(k, []).append(v)
    for k, v in values.items():
        if len(v) >= 2:
            q1, med, q3 = statistics.quantiles(v, n=4)
            print(f"spread {k}: n {len(v)} median {statistics.median(v)!r} "
                  f"iqr/median {(q3 - q1) / statistics.median(v)!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
