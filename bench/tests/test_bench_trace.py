"""The reduction from a profiler trace to per-layer metrics, on a small
synthetic trace whose answers are counted by hand."""
import types

import pytest

from bench import harness
from bench import trace as T


def _trace():
    # chip 0: a while (0-100) holding two ops, then one op; host annotation
    ops0 = T.leaf_ops([(0, 100, "while"), (0, 30, "fusion.1"),
                       (50, 20, "all-gather.2"), (150, 50, "copy.3")])
    ops1 = T.leaf_ops([(10, 40, "fusion.1"), (160, 20, "copy.3")])
    host = [(0, 400, "bench.window"), (90, 60, "PjitFunction(step)")]
    return T.Trace(ops={0: ops0, 1: ops1}, host=host,
                   window_s=400e-9)


def test_leaf_ops_drop_containers():
    assert [n for _, _, n in _trace().ops[0]] == ["fusion.1", "all-gather.2",
                                                  "copy.3"]


def test_busy_and_idle():
    tr = _trace()
    assert tr.busy_s(0) == pytest.approx(100e-9)
    assert tr.busy_s(1) == pytest.approx(60e-9)
    assert tr.mean_busy_s() == pytest.approx(80e-9)
    read = harness.load_reader("idle_share.train")
    ctx = harness.ReadContext(trace=tr, info={}, cell=None, peaks=None)
    assert read(ctx) == pytest.approx(100 * (1 - 80 / 400))


def test_idle_gaps_named_by_host_span():
    gaps = _trace().idle_gaps(0)
    # gaps: 30-50 (20, middle 40: window only), 70-150 (80, middle 110:
    # inside the PjitFunction span)
    assert gaps[0] == ["host:PjitFunction(step)", pytest.approx(80e-9)]
    assert gaps[1] == ["host:bench.window", pytest.approx(20e-9)]


def test_op_seconds_mean_over_chips():
    secs = _trace().op_seconds(lambda n: n.split(".")[0])
    assert secs["fusion"] == pytest.approx((30 + 40) / 2 * 1e-9)
    assert secs["copy"] == pytest.approx((50 + 20) / 2 * 1e-9)


def test_hlo_op_names():
    text = ('  %fusion.8 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, '
            'metadata={op_name="jit(f)/c012/psum" stack_frame_id=3}\n'
            '  ROOT %all-reduce.1 = f32[8] all-reduce(%x), metadata='
            '{op_name="jit(f)/c013/psum"}\n  %p = f32[8] parameter(0)\n')
    assert T.hlo_op_names(text) == {"fusion.8": "jit(f)/c012/psum",
                                    "all-reduce.1": "jit(f)/c013/psum"}
    assert T.hlo_name("%copy.15 = f32[2] copy(f32[2] %x)") == "copy.15"


def test_grid_readers_on_a_synthetic_trace():
    # two calls: allgather of 8 B (small) and of 2 MiB (large); 2 ladders
    calls = [("allgather", 8), ("allgather", 2 * 2**20)]
    cell = types.SimpleNamespace(calls=calls, p=4,
                                 call_of={"a": 0, "b": 1}.get)
    ops = [(0, 10_000, "a"), (20_000, 100_000, "b"),
           (200_000, 10_000, "a"), (220_000, 100_000, "b")]
    tr = T.Trace(ops={0: ops}, host=[], window_s=400e-6)
    peaks = harness.peaks("TPU v5 lite")
    ctx = harness.ReadContext(trace=tr, info={"ladders": 2}, cell=cell,
                              peaks=peaks)
    assert harness.load_reader("lat_small_us.grid")(ctx) == pytest.approx(10)
    # 3 x 2 MiB must reach a chip at 200 GB/s: 31.46 us of each 100 us
    want = 100 * 3 * 2 * 2**20 / 200e9 / 100e-6
    assert harness.load_reader("ici_roofline.grid")(ctx) == pytest.approx(want)


def test_readers_return_nothing_without_device_ops():
    tr = T.Trace(ops={}, host=[], window_s=1.0)
    ctx = harness.ReadContext(trace=tr, info={"steps": 3, "ladders": 2},
                              cell=types.SimpleNamespace(calls=[], p=4,
                                                         call_of=dict().get),
                              peaks=None)
    for name in ("idle_share.train", "idle_share.grid", "mfu.train",
                 "lat_small_us.grid", "ici_roofline.grid"):
        assert harness.load_reader(name)(ctx) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
