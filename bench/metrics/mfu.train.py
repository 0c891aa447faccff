"""Model FLOP utilisation of the whole training step: the forward and
backward FLOPs of the steps run in the traced window (the reference
module's ``flops_per_token``, three times, no recompute counted), over
the window's length, the cell's chips and the chip's bf16 peak."""


def read(ctx):
    info = ctx.info
    if ctx.peaks is None or ctx.trace.window_s <= 0 or not info.get("steps"):
        return None
    peak = info["chips"] * ctx.peaks["bf16_flops"]
    return 100.0 * info["model_flops"] / ctx.trace.window_s / peak
