"""Executables the trainer built in ``step`` calls after its first one,
read after the traced window (``Trainer.recompiles``, counted from JAX's
backend-compile event, which a load from the persistent cache fires too).
0 when the step program is built once; nothing from a trainer that does
not count."""


def read(ctx):
    n = getattr(getattr(ctx.cell, "trainer", None), "recompiles", None)
    return None if n is None else int(n)
