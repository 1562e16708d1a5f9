"""The trace reduction on synthetic events and on a small trace recorded
on one TPU v5e (``data/small.xplane.pb``: three 4,096-address
``gather_read`` launches and three fused store commits inside a
``bench.window`` span)."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce as tr  # noqa: E402

RECORDED = BENCH / "tests" / "data" / "small.xplane.pb"


def _device(programs, ops):
    return [{"name": "/device:TPU:0", "programs": programs, "ops": ops}]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    host = [("bench.window", 100.0, 1100.0),
            ("bench.audit_chunk", 150.0, 400.0),
            ("bench.transfer", 600.0, 1000.0)]
    programs = [("jit__gather(7)", 200.0, 400.0),
                ("jit__commit_fused_jit(9)", 700.0, 800.0),
                ("jit_other", 1050.0, 1300.0)]
    ops = [("custom-call.1", 210.0, 300.0),    # the gather kernel
           ("fusion.2", 250.0, 390.0),         # overlaps it
           ("custom-call.4", 700.0, 760.0),    # the commit kernel
           ("copy.3", 1050.0, 1300.0)]         # runs past the window
    out = tr.reduce_events(_device(programs, ops), host)
    assert out["window_s"] == pytest.approx(1000e-9)
    # [210, 390] + [700, 760] + [1050, 1100]
    assert out["busy_s"] == pytest.approx((180 + 60 + 50) * 1e-9)
    k = out["kernels"]
    assert k["gather_read"]["calls"] == 1
    assert k["gather_read"]["time_s"] == pytest.approx(90e-9)
    assert k["commit_fused"]["time_s"] == pytest.approx(60e-9)
    gaps = out["breakdown"]["idle_gaps"]
    # idle [390, 700], [760, 1050] and [100, 210], longest first
    assert gaps == [["bench.audit_chunk+bench.transfer",
                     pytest.approx(310e-9)],
                    ["bench.transfer", pytest.approx(290e-9)],
                    ["bench.audit_chunk", pytest.approx(110e-9)]]
    assert out["breakdown"]["device_ops"][0][0] == "_gather:fusion"


def test_missing_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events(_device([], []), [("bench.audit", 0.0, 1.0)])


def test_no_device_reads_idle():
    out = tr.reduce_events([], [("bench.window", 0.0, 1e9)])
    assert out["busy_s"] == 0.0
    assert out["breakdown"]["idle_gaps"] == [["no bench span", 1.0]]


def test_recorded_v5e_trace():
    devices, host = tr.load(str(RECORDED))
    out = tr.reduce_events(devices, host)
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["kernels"]["gather_read"]["calls"] == 3
    assert out["kernels"]["commit_fused"]["calls"] == 3
    assert out["kernels"]["gather_read"]["time_s"] > 0
    assert len(out["breakdown"]["device_ops"]) <= tr.TOP
    assert len(out["breakdown"]["idle_gaps"]) <= tr.TOP
