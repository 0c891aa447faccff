"""The zamba2 cell's training step against its reference on the CPU at
small widths, under the reference's own ``init_leaf``: Mamba2's published
decay rates ``exp(a_log)`` in [1, 16] and steps ``softplus(dt_bias)`` in
[1e-3, 1e-1].  Two periods, so the shared block's weights are used twice
and their gradient adds up over both uses.

In float32 the program is the reference's equations: every number agrees
to round-off.  In bfloat16 every number is finite and the losses and the
typical leaf agree; the worst leaves are the bf16 rounding of the first
layer's step and skip gradients (PERF.md, section 6), which the chip's
limits, not these, bound.
"""
import json
import pathlib

import jax
import numpy as np
import pytest

from bench.kinds import train

BENCH = pathlib.Path(__file__).parents[1]
TINY = {"n_layers": 12, "d_model": 128, "d_ff": 256, "vocab_size": 512,
        "n_heads": 2, "n_kv_heads": 2}
#: dtype -> the largest each compared number may read: round-off in
#: float32; in bfloat16, the losses and the median leaf
BOUNDS = {"float32": {"loss_worst": 1e-5, "grad_gap": 1e-4,
                      "grad_worst": 1e-4, "change_worst": 1e-3},
          "bfloat16": {"loss_worst": 3e-3, "grad_median": 0.05,
                       "change_gap": 0.02}}


def _cell(dtype, seed):
    cfg = json.loads((BENCH / "configs" / "zamba2-1.2b-proj-shared.json")
                     .read_text())
    tiny = dict(TINY, dtype=dtype)
    cfg = dict(cfg, **tiny, overrides=sorted(set(cfg["overrides"]) | set(tiny)))
    traffic = json.loads((BENCH / "traffic" / "train-2x2048-1x1.json")
                         .read_text())
    traffic = dict(traffic, global_batch=2, seq=64)
    return train.Cell(cfg, traffic, {}, seed=seed, devices=jax.devices()[:1])


@pytest.mark.parametrize("dtype", sorted(BOUNDS))
def test_program_agrees_with_reference_at_published_init(dtype):
    cell = _cell(dtype, 2**31 + 15)
    cell.setup()
    prog = cell.prog
    cell.release()
    assert cell.failed == 0
    for what in ("losses", "grad", "change"):
        assert np.all(np.isfinite(prog[what])), what
    g = train.gaps(prog, cell.reference_run())
    for name, bound in BOUNDS[dtype].items():
        assert g[name] <= bound, (name, g)
