"""For calls of at least 256 KiB a rank: the least time their bytes need
at the chip's interconnect peak, over the device time they took.

The bytes are those the busiest chip must move by the op's semantics
(``bench/refs/mpi.link_bytes``), whichever schedule runs, so a schedule
that moves more scores lower and no schedule scores over 100 %."""

from bench.refs import mpi

LARGE = 256 * 1024


def read(ctx):
    cell, tr = ctx.cell, ctx.trace
    large = [i for i, (_, nb) in enumerate(cell.calls) if nb >= LARGE]
    if not tr.ops or not large or ctx.peaks is None:
        return None
    lset = set(large)
    secs = tr.op_seconds(lambda n: "l" if cell.call_of(n) in lset else None)
    if not secs.get("l"):
        return None
    bw = ctx.peaks["ici_bytes_per_s"]
    t_min = sum(mpi.link_bytes(cell.calls[i][0], cell.p, cell.calls[i][1]) / bw
                for i in large)
    return 100.0 * t_min * ctx.info["ladders"] / secs["l"]
