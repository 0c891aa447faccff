#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (load, compile, warm up, the first checked steps) is timed as
``setup_s``; then the cell runs for ``--seconds`` with the profiler off
(``--trace 0``: the cell's end-to-end metrics) or on (``--trace 1``: its
per-layer metrics).  After the window the program's output is compared
with the cell's plain reference, which decides ``correct``.
"""
import pathlib
import sys
import time

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
