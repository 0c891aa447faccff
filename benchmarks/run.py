# One function per paper table/figure. Prints ``name,us_per_call,derived``.
from __future__ import annotations

import sys
import traceback

from benchmarks.common import header


def main() -> int:
    """Runs every table; returns 1 when any of them raised."""
    header()
    from benchmarks import (bench_case_allreduce, bench_case_reduce,
                            bench_collective_matmul, bench_decode_profile,
                            bench_dispatch, bench_guidelines, bench_hierarchy,
                            bench_measured, bench_nrep_lookup, bench_roofline)
    failed = []
    for mod in (bench_guidelines,       # Figs. 3/4/5 violation tables
                bench_case_reduce,      # Fig. 6 Reduce<=Allreduce case
                bench_case_allreduce,   # Fig. 7 rs+agv beats everything
                bench_collective_matmul,  # fused-vs-unfused overlap model
                bench_dispatch,         # api._select fast-path overhead
                bench_nrep_lookup,      # Alg.1/Eq.1 + O(log M) lookup
                bench_measured,         # ReproMPI-style measured pipeline
                bench_roofline,         # §Roofline per dry-run cell
                bench_hierarchy,        # per-axis tiers + hier must-wins
                bench_decode_profile):  # trace-replay serving (smoke)
        try:
            mod.run()
        except Exception:
            traceback.print_exc()
            print(f"{mod.__name__},0.0,ERROR")
            failed.append(mod.__name__)
    if failed:
        print(f"benchmarks failed: {failed}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
