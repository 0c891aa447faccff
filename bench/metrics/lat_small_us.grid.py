"""Mean device time of one call with a payload of at most 32 KiB a rank:
the device seconds of the instructions each such call compiled to, over
the calls run in the traced window (device trace)."""

SMALL = 32 * 1024


def read(ctx):
    cell, tr = ctx.cell, ctx.trace
    small = {i for i, (_, nb) in enumerate(cell.calls) if nb <= SMALL}
    if not tr.ops or not small:
        return None
    secs = tr.op_seconds(lambda n: "s" if cell.call_of(n) in small else None)
    if "s" not in secs:
        return None
    return secs["s"] / (ctx.info["ladders"] * len(small)) * 1e6
