"""The two readers ``vitdet-b-mask`` brings — ``attn_device_ms`` and
``attn_roofline.serve`` — on a hand-made trace, on a trace that holds none
of the kernel's ops (the parent's program), and on a configuration that
names no such kernel."""

import copy

import pytest

from benchmark import harness, xplane

SPEC = harness.load_cell("vitdet-serve-closed")
CELL = "vitdet-serve-closed"
MS = 1e6      # ns
NAMES = ("attn_device_ms", "attn_roofline.serve")


def op(name, start_ms, dur_ms, custom=True):
    text = f"%{name} = bf16[96,4096,64] " + (
        "custom-call(bf16[96,4096,192] %a)" if custom else "fusion(%a)")
    return (text, start_ms * MS, dur_ms * MS)


def trace(with_kernel=True):
    """Two executions of the predict program inside the window: each runs
    the four global blocks' kernel once (10, 11, 12 and 13 ms, then 12, 13,
    14 and 15 ms), the two NMS calls eight times, and other work."""
    ops = []
    for run, (t0, extra) in enumerate(((100, 0), (500, 2))):
        if with_kernel:
            for i in range(4):
                ops.append(op(f"vit_global_attention.{4 + i}", t0 + 20 * i,
                              10 + i + extra))
        for i in range(8):
            ops.append(op("closed_call.19", t0 + 100 + 3 * i, 1.0))
            ops.append(op("closed_call.20", t0 + 101.5 + 3 * i, 0.5))
        ops.append(op("fusion.7", t0 + 200, 50, custom=False))
        ops.append(op("custom-call.92", t0 + 260, 2))
    planes = {
        "/host:CPU": {"main": [(xplane.MARK_BEGIN, 50 * MS, 1),
                               (xplane.MARK_END, 1000 * MS, 1)]},
        "/device:TPU:0": {xplane.OPS_LINE: ops, xplane.MODULES_LINE: [
            ("jit_fwd_wf(1)", 100 * MS, 300 * MS),
            ("jit_fwd_wf(1)", 500 * MS, 300 * MS)]}}
    return xplane.reduce(planes, 1)


def ctx(red, config=None):
    config = config or SPEC["config"]
    return {"trace": red, "config": config, "cell": SPEC["cell"],
            "traffic": SPEC["traffic"],
            "peaks": harness.peaks_for("TPU v5 lite"),
            "metrics_before": {}, "metrics_after": {}, "window": {},
            "flops": harness.modules_of(config)["flops"]}


def read(name, c):
    bench = {"per_layer": [m for m in SPEC["bench"]["per_layer"]
                           if m["name"] == name]}
    assert len(bench["per_layer"]) == 1
    return harness.read_layers(bench, CELL, c).get(name)


def test_the_kernels_time_a_batch_and_its_share_of_the_roofline_by_hand():
    """A batch: the four instructions' mean durations, 11 + 12 + 13 + 14 =
    50 ms.  An image: 50 / 8 = 6.25 ms against the least the mathematics
    takes, 4 x 12 heads x 2 x 2 x 4096^2 x 64 FLOP / 197e12 = 1.0466 ms:
    16.75 %."""
    c = ctx(trace())
    assert read("attn_device_ms", c) == {"value": pytest.approx(50.0),
                                         "unit": "ms"}
    least_ms = 4 * 12 * 2 * 2 * 4096 ** 2 * 64 / 197e12 * 1e3
    assert read("attn_roofline.serve", c) == {
        "value": pytest.approx(100 * least_ms / 6.25), "unit": "%"}
    assert 16.7 < read("attn_roofline.serve", c)["value"] < 16.8


def test_the_nms_reader_takes_the_nms_calls_alone_in_this_cell():
    """``nms_roofline.serve`` with this configuration's ``nms_kernel``: the
    two NMS instructions' means, 1.0 + 0.5 ms an image — with
    ``r101-fpn-mask``'s pattern it would add the attention's 50 ms and the
    stray custom call's 2."""
    c = ctx(trace())
    mine = read("nms_roofline.serve", c)["value"]
    work = c["flops"].nms_work(4768, 1000)
    least, _ = c["flops"].roofline_seconds(work["ops"], work["bytes"],
                                           c["peaks"])
    assert mine == pytest.approx(100 * least / 1.5e-3)
    other = copy.deepcopy(SPEC["config"])
    other["names"]["nms_kernel"] = "custom-call$"
    assert read("nms_roofline.serve", ctx(trace(), other))["value"] == \
        pytest.approx(100 * least / 53.5e-3)
    assert read("predict_device_ms", c)["value"] == pytest.approx(300.0)
    mfu = read("predict_mfu", c)["value"]
    assert mfu == pytest.approx(
        100 * 8 * c["flops"].predict_flops_per_image(
            SPEC["config"]["net"])["total"] / (0.3 * 197e12))
    assert 20 < mfu < 22


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_kernel_gives_none(name):
    assert read(name, ctx(trace(with_kernel=False))) is None
    empty = {"modules": {}, "op_time": {}}
    assert read(name, ctx(empty)) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_configuration_that_names_no_attention_kernel_gives_none(name):
    mask = harness.load_cell("mask-serve-closed")["config"]
    assert "attn_kernel" not in mask["names"]
    assert read(name, ctx(trace(), mask)) is None
    entry = next(m for m in SPEC["bench"]["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
