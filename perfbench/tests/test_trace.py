"""The reduction from trace records to busy time, kernel time and the
breakdown, on hand-made records and on a small trace recorded on a v5e
(``perfbench/tools/record_trace.py``)."""

from pathlib import Path

import pytest

from harness import trace

DATA = Path(__file__).resolve().parent / "data"


def _records():
    # one device; ops (name, start_ns, dur_ns, is_kernel, label)
    ops = [("fusion.1", 1000, 1000, False, "jit_f/fusion"),
           ("custom-call.2", 1500, 2000, True, "jit_f/spmm"),   # overlaps
           ("copy.3", 6000, 1000, False, "jit_f/copy"),
           ("fusion.1", 9000, 500, False, "jit_f/fusion")]
    host = [("bench.window", 0, 10000),
            ("bench.call", 3500, 1000),       # covers part of gap 3500-6000
            ("bench.block", 4000, 4000)]      # nested later: wins 4000-6000
    return {"devices": {"/device:TPU:0": ops}, "host": host}


def test_busy_union_and_window():
    r = trace.reduce(_records())
    # union: [1000, 3500] + [6000, 7000] + [9000, 9500] = 4000 ns
    assert r["busy_s"] == pytest.approx(4000e-9)
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["kernel_s"] == pytest.approx(2000e-9)


def test_breakdown_by_op_and_by_host_span():
    b = trace.reduce(_records())["breakdown"]
    ops = dict(b["device_ops"])
    assert ops["jit_f/spmm"] == pytest.approx(2000e-9)
    assert ops["jit_f/fusion"] == pytest.approx(1500e-9)
    gaps = dict(b["idle_gaps"])
    # gaps: [0,1000] unmarked; [3500,6000]: call 3500-4000, block
    # 4000-6000; [7000,9000] block 7000-8000, unmarked 8000-9000;
    # [9500,10000] unmarked
    assert gaps["bench.call"] == pytest.approx(500e-9)
    assert gaps["bench.block"] == pytest.approx(3000e-9)
    assert gaps["host.unmarked"] == pytest.approx(2500e-9)
    assert sum(gaps.values()) == pytest.approx(6000e-9)


def test_two_devices_average():
    rec = _records()
    rec["devices"]["/device:TPU:1"] = [("fusion.1", 0, 10000, False, "x")]
    r = trace.reduce(rec)
    assert r["busy_s"] == pytest.approx((4000e-9 + 10000e-9) / 2)


def test_no_device_reads_nothing():
    r = trace.reduce({"devices": {}, "host": []})
    assert r["busy_s"] is None and r["breakdown"] is None


def test_recorded_v5e_trace():
    path = DATA / "small_trace.xplane.pb"
    rec = trace.load(str(path))
    assert list(rec["devices"]) == ["/device:TPU:0"]
    assert {n for n, _, _ in rec["host"]} >= {"bench.window", "bench.call"}
    r = trace.reduce(rec)
    # two calls: the forward kernel (jvp), its transpose-side SpMM and the
    # SDDMM are the custom calls; the window is the host's bench.window
    assert r["window_s"] == pytest.approx(1.084106e-3)
    assert r["busy_s"] == pytest.approx(1.04557e-4)
    assert r["kernel_s"] == pytest.approx(7.2026e-5)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["jit_call/jvp__"] == pytest.approx(3.1274e-5)
    assert r["breakdown"]["device_ops"][0][0] == "jit_call/transpose_jvp___"
    assert len(r["breakdown"]["device_ops"]) <= 10
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_op_label():
    name = ('%jvp__.1 = f32[1,8,384,256]{3,2,1,0} custom-call(s32[24]{0} '
            '%constant.29), custom_call_target="tpu_custom_call"')
    assert trace.op_label(name, "jit_call(123)") == "jit_call/jvp__"
    assert trace._is_kernel(name, {})
    assert not trace._is_kernel('%fusion.2 = f32[2] fusion(%custom-call.1)',
                                {})
