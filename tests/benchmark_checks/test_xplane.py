"""``benchmark/xplane.py``: the reduction on hand-made events (busy union,
idle share, a kernel's time, the gaps' labels) and on the small trace
recorded on the chip and kept under ``benchmark/testdata/``."""

import os

import pytest

from benchmark import harness, xplane

MS = 1e6  # ns


def planes():
    dev = {
        xplane.OPS_LINE: [
            ("%fusion.1 = bf16[4,4] fusion(bf16[4,4] %p)", 10 * MS, 20 * MS),
            ("fusion.2", 25 * MS, 15 * MS),       # 25-40 overlaps
            ("%closed_call.3 = s32[8,2] custom-call(f32[8,4] %a)", 60 * MS, 5 * MS),
            ("%closed_call.3 = s32[8,2] custom-call(f32[8,4] %a)", 70 * MS, 5 * MS),
            ("fusion.9", 95 * MS, 20 * MS),       # 95-115, clipped at 100
        ],
        xplane.MODULES_LINE: [
            ("jit_f(123)", 10 * MS, 30 * MS),
            ("jit_f(123)", 60 * MS, 15 * MS),
            ("jit_other(9)", 95 * MS, 20 * MS),   # not whole: left out
        ],
    }
    host = {"main": [(xplane.MARK_BEGIN, 0.0, 1.0),
                     (xplane.MARK_END, 100 * MS, 1.0),
                     ("TransferToDevice", 41 * MS, 18 * MS),
                     ("$python frame", 0.0, 100 * MS)],
            "python": [("whatever", 0.0, 100 * MS)]}
    return {"/device:TPU:0": dev, "/device:TPU:1": dev, "/host:CPU": host}


def test_busy_union_and_idle_share():
    red = xplane.reduce(planes(), chips=1)
    assert red["window_s"] == pytest.approx(0.100)
    # 10-40, 60-65, 70-75, 95-100 = 30 + 5 + 5 + 5
    assert red["busy_s"] == pytest.approx(0.045)
    assert red["busy_worst_s"] == pytest.approx(0.045)
    assert red["devices"] == ["/device:TPU:0"]


def test_programs_and_a_kernels_time():
    red = xplane.reduce(planes(), chips=2)
    assert red["devices"] == ["/device:TPU:0", "/device:TPU:1"]
    assert xplane.module_times(red, r"^jit_f") == pytest.approx([0.030, 0.015])
    assert xplane.module_times(red, r"^jit_other") == []
    total, n = xplane.op_seconds(red, "custom-call$")
    assert (total, n) == (pytest.approx(0.010), 2)
    assert xplane.op_seconds(red, "absent") == (0, 0)
    fams = dict(red["device_ops"])
    assert fams["fusion"] == pytest.approx(0.040)      # .1 + .2 + clipped .9
    assert fams["closed_call custom-call"] == pytest.approx(0.010)


def test_idle_gaps_are_labelled_by_what_the_host_did():
    red = xplane.reduce(planes(), chips=1)
    gaps = dict(red["idle_gaps"])
    # 40-60 is mostly under TransferToDevice; 0-10, 65-70, 75-95 under
    # nothing but a python frame and the markers
    assert gaps["TransferToDevice"] == pytest.approx(0.020)
    assert gaps["unattributed"] == pytest.approx(0.035)
    assert len(red["idle_gaps"]) <= 10


def test_without_markers_the_device_events_span_the_window():
    p = planes()
    p["/host:CPU"]["main"] = []
    red = xplane.reduce(p, chips=1)
    assert red["window_s"] == pytest.approx(0.105)     # 10 .. 115


def test_a_trace_without_a_device_is_refused():
    with pytest.raises(RuntimeError):
        xplane.reduce({"/host:CPU": {"main": []}}, chips=1)


def test_family_strips_the_numbering():
    assert xplane.family("%fusion.123") == "fusion"
    assert xplane.family("convolution_7") == "convolution"
    assert xplane.family("nms") == "nms"
    assert xplane.short("%closed_call.19 = s32[8192,256]{1,0} custom-call("
                        "f32[1] %g)") == "closed_call.19 custom-call"
    assert xplane.family("closed_call.19 custom-call") == \
        "closed_call custom-call"


RECORDED = os.path.join(harness.HERE, "testdata", "tiny_tpu.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in benchmark/testdata")
def test_the_recorded_chip_trace():
    expect = harness.load_json(os.path.join(harness.HERE, "testdata",
                                            "tiny_tpu.expect.json"))
    loaded = xplane.load(RECORDED)
    red = xplane.reduce(loaded, chips=1)
    assert red["devices"] == expect["devices"]
    assert red["window_s"] == pytest.approx(expect["window_s"], rel=1e-6)
    assert red["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-6)
    assert 0 < red["busy_s"] < red["window_s"]
    total, n = xplane.op_seconds(red, expect["kernel"])
    assert n == expect["kernel_count"]
    assert total == pytest.approx(expect["kernel_s"], rel=1e-6)
