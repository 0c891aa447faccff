"""Model-FLOP and collective-byte counts against hand counts."""
import pytest

from bench.refs import mpi, rwkv6


def test_rwkv6_flops_per_token_by_hand():
    cfg = {"d_model": 8, "d_ff": 16, "n_heads": 2, "vocab_size": 32,
           "n_layers": 3, "ssm": {"head_dim": 4, "decay_lora_rank": 2}}
    # per layer, multiply-adds: r k v g 4*8*8=256, decay LoRA 8*2+2*8=32,
    # out 8*8=64, channel mix 8*16+16*8=256 and 8*8=64: 672 -> 1344 FLOPs;
    # recurrence 4 * 2 heads * 4^2 = 128; head 2*8*32 = 512
    assert rwkv6.flops_per_token(cfg, 1024) == 3 * (1344 + 128) + 512


def test_rwkv6_3b_cut_matches_its_parameter_count():
    import json
    import pathlib
    cfg = json.loads((pathlib.Path(__file__).parents[1] / "configs"
                      / "rwkv6-3b-static-mix.json").read_text())
    # 2 FLOPs per non-embedding weight, plus the recurrence (2.6 MFLOP)
    f = rwkv6.flops_per_token(cfg, 1024)
    assert f == pytest.approx(2 * 510.1e6 + 2.6e6, rel=2e-3)


@pytest.mark.parametrize("op,want", [
    ("allgather", 3 * 1024), ("allreduce", 1.5 * 1024),
    ("reducescatter", 3 * 1024), ("alltoall", 3 * 1024),
    ("bcast", 1024), ("gather", 3 * 1024), ("scatter", 3 * 1024),
    ("reduce", 1024), ("scan", 1024), ("exscan", 1024)])
def test_link_bytes_by_hand(op, want):
    assert mpi.link_bytes(op, 4, 1024) == want


def test_train_gaps_by_hand():
    from bench.kinds import train
    prog = {"losses": [10.1, 9.0, 8.0], "grad": [3.0, 4.0, 1e-9],
            "change": [1.0, 2.0, 5.0]}
    ref = {"losses": [10.0, 9.0, 8.8], "grad": [3.0, 4.0, 1e-9],
           "change": [1.0, 2.5, 0.0]}
    g = train.gaps(prog, ref)
    assert g["loss_gap"] == pytest.approx(0.01)          # first step only
    assert g["loss_worst"] == pytest.approx(0.8 / 8.8)
    assert g["grad_gap"] == pytest.approx(0.0)           # 5 against 5
    # the third leaf's gradient is under 1e-3 of the median: left out;
    # median of the kept change gaps 0/max(1,1.75) and 0.5/max(2.5,1.75)
    assert g["change_gap"] == pytest.approx((0 + 0.2) / 2)
    assert g["change_worst"] == pytest.approx(0.2)
