"""The zamba2 reference against the program's training step on the CPU,
at small widths.

With decay rates like those the program initialises (``a = 1``, steps
``dt = 0.01``) the two agree.  With Mamba2's published initialisation
(``a`` in [1, 16], ``dt`` in [1e-3, 0.1]) the program's gradients come
out NaN while the reference's stay finite: see PERF.md, Open questions.
The zamba2 cell waits for that fault to be repaired.
"""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from bench.kinds import train
from bench.refs import zamba2

BENCH = pathlib.Path(__file__).parents[1]
TINY = {"n_layers": 6, "d_model": 128, "d_ff": 256, "vocab_size": 512,
        "n_heads": 2, "n_kv_heads": 2}


def _cell(monkeypatch, seed):
    cfg = json.loads((BENCH / "configs" / "zamba2-1.2b.json").read_text())
    cfg = dict(cfg, **TINY, overrides=sorted(set(cfg["overrides"]) | set(TINY)))
    traffic = json.loads((BENCH / "traffic" / "train-2x2048-1x1.json")
                         .read_text())
    traffic = dict(traffic, global_batch=2, seq=64)
    base = zamba2.init_leaf

    def small_decay(name, shape, dtype, key):
        if name == "a_log":
            return jnp.zeros(shape, dtype)
        if name == "dt_bias":
            return jnp.full(shape, np.log(np.expm1(0.01)), dtype)
        return base(name, shape, dtype, key)

    monkeypatch.setattr(zamba2, "init_leaf", small_decay)
    return train.Cell(cfg, traffic, {}, seed=seed, devices=jax.devices()[:1])


def test_reference_agrees_with_the_program_at_small_decay(monkeypatch):
    cell = _cell(monkeypatch, 2**31 + 5)
    cell.setup()
    cell.release()
    g = train.gaps(cell.prog, cell.reference_run())
    assert g["loss_gap"] < 2e-3 and g["grad_gap"] < 0.1 and \
        g["change_gap"] < 0.1, g


def test_flops_per_token_by_hand():
    cfg = {"d_model": 8, "n_heads": 2, "head_dim": 4, "d_ff": 16,
           "vocab_size": 32, "n_layers": 6, "hybrid_period": 6,
           "ssm": {"expand": 2, "state_dim": 2, "head_dim": 4}}
    # mamba: di 16, 4 heads; in-proj 8*(32+4+4)=320, out 16*8=128 -> 896
    # FLOPs, recurrence 4*4*2*4=128; shared block: proj 2*8*8=128, attn
    # 4*8*8=256, mlp 3*8*16=384 -> 1536, scores and values 2*S*8
    seq = 10
    want = 6 * (896 + 128) + (1536 + 2 * seq * 8) + 2 * 8 * 32
    assert zamba2.flops_per_token(cfg, seq) == want
