"""The two readers ``r101-fpn-cascade-mask`` brings — ``roi_cascade_device_ms``
and ``roi_cascade_mfu`` — on a hand-made trace of the three programs a
batch runs, on a trace that holds no execution of the stages' program (the
parent's, which has none), and on a configuration that names no such
program; and the accepted readers of the predict program beside them."""

import copy

import pytest

from benchmark import harness, xplane

SPEC = harness.load_cell("cascade-serve-closed")
CELL = "cascade-serve-closed"
MS = 1e6      # ns
NAMES = ("roi_cascade_device_ms", "roi_cascade_mfu")


def trace(with_stages=True):
    """Two batches inside the window: predict (100 and 98 ms), the stages'
    program right behind it (80 and 84 ms), the mask program (13 ms)."""
    modules = []
    for t0, (p, c) in ((100, (100, 80)), (600, (98, 84))):
        modules.append(("jit_fwd_wf(1)", t0 * MS, p * MS))
        if with_stages:
            modules.append(("jit_roi_cascade(3)", (t0 + p) * MS, c * MS))
        modules.append(("jit_mask_branch(7)", (t0 + 300) * MS, 13 * MS))
    ops = [("%fusion.1 = bf16[8,1000,1024] fusion(%a)", 110 * MS, 5 * MS)]
    planes = {
        "/host:CPU": {"main": [(xplane.MARK_BEGIN, 50 * MS, 1),
                               (xplane.MARK_END, 1000 * MS, 1)]},
        "/device:TPU:0": {xplane.OPS_LINE: ops,
                          xplane.MODULES_LINE: modules}}
    return xplane.reduce(planes, 1)


def ctx(red, config=None):
    config = config or SPEC["config"]
    return {"trace": red, "config": config, "cell": SPEC["cell"],
            "traffic": SPEC["traffic"],
            "peaks": harness.peaks_for("TPU v5 lite"),
            "metrics_before": {}, "metrics_after": {}, "window": {},
            "flops": harness.modules_of(config)["flops"]}


def read(name, c, cell=CELL):
    bench = {"per_layer": [m for m in SPEC["bench"]["per_layer"]
                           if m["name"] == name]}
    assert len(bench["per_layer"]) == 1
    return harness.read_layers(bench, cell, c).get(name)


def test_the_stages_device_time_is_their_programs_mean():
    got = read("roi_cascade_device_ms", ctx(trace()))
    assert got == {"value": pytest.approx(82.0), "unit": "ms"}
    # predict's reader reads predict alone: ^jit_f is not jit_roi_cascade
    assert read("predict_device_ms", ctx(trace()))["value"] == \
        pytest.approx(99.0)
    # and the mask program's (a reader this cell does not list yet:
    # PERF.md section 7 row 11) its own
    assert read("mask_device_ms", ctx(trace()),
                "mask-serve-closed")["value"] == pytest.approx(13.0)


def test_the_stages_share_of_the_peak_by_hand():
    """453.8 GFLOP a batch of 8 in 82 ms against 197 TFLOP/s: 2.81 %."""
    got = read("roi_cascade_mfu", ctx(trace()))
    work = 8 * SPEC["config"]["net"]["test_post_nms"] * (
        2 * 2 * 13_980_672 + 2 * 2 * 4 * 49 * 4 * 256)
    assert got["unit"] == "%"
    assert got["value"] == pytest.approx(100 * work / (82e-3 * 197e12))
    assert got["value"] == pytest.approx(2.809, abs=0.002)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_gives_none(name):
    # the parent's program: predict and the mask branch, no stages
    assert read(name, ctx(trace(with_stages=False))) is None
    # a configuration that names no such program
    other = copy.deepcopy(SPEC["config"])
    del other["names"]["roi_cascade_program"]
    assert read(name, ctx(trace(), other)) is None
    # nothing traced at all
    assert read(name, ctx({"modules": {}, "op_time": {}})) is None


def test_the_entries():
    for name, unit, better in (("roi_cascade_device_ms", "ms", "lower"),
                               ("roi_cascade_mfu", "%", "higher")):
        m = next(m for m in SPEC["bench"]["per_layer"] if m["name"] == name)
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"], m["workloads"]) == (
            unit, better, "device_trace", "RoI cascade", "serve_imgs_per_s",
            [CELL])
    # appended at the end of the list, after every accepted entry
    assert [m["name"] for m in SPEC["bench"]["per_layer"]][-2:] == \
        list(NAMES)
