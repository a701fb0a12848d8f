"""The five per-layer readers ``r101-fpn-mask`` brings, each on a hand-made
``ctx``: the value worked out by hand, and None — never 0, never an error —
where its span, counter or program is absent (the parent of this PR, a
network without a mask head, an untraced or empty window)."""

import copy

import pytest

from benchmark import harness

CELL = "mask-serve-closed"
SPEC = harness.load_cell(CELL)
NAMES = ("turn_mask_ms", "turn_mask_paste_ms", "mask_device_ms", "mask_mfu",
         "masks_per_img")

BEFORE = {
    "counters": {"served": 16, "batches": 2, "mask_rois": 1600,
                 "mask_dispatches": 2},
    "stages": {"serve/mask": {"count": 2, "sum_s": 0.5},
               "serve/mask/paste": {"count": 2, "sum_s": 0.3}}}
AFTER = {
    "counters": {"served": 96, "batches": 12, "mask_rois": 9100,
                 "mask_dispatches": 12},
    "stages": {"serve/mask": {"count": 12, "sum_s": 2.5},
               "serve/mask/paste": {"count": 12, "sum_s": 1.5}}}
TRACE = {"modules": {"jit_mask_branch(123)": [0.020, 0.030],
                     "jit_fwd_wf(7)": [0.098]},
         "op_time": {}, "busy_s": 1.0, "window_s": 4.0}
# 750 records a dispatch x 2 x 530,059,264 MAC over 25 ms at 197 T/s
MFU = 100.0 * 2 * 530059264 * 750 / (0.025 * 197e12)
WANT = {"turn_mask_ms": 200.0, "turn_mask_paste_ms": 120.0,
        "mask_device_ms": 25.0, "mask_mfu": MFU, "masks_per_img": 93.75}


def ctx(before=BEFORE, after=AFTER, trace=TRACE, config=None):
    config = config or SPEC["config"]
    return {"trace": trace, "window": {}, "config": config,
            "traffic": SPEC["traffic"], "cell": SPEC["cell"],
            "peaks": harness.peaks_for("TPU v5 lite"),
            "metrics_before": before, "metrics_after": after,
            "flops": harness.modules_of(config)["flops"]}


def read(name, c):
    bench = {"per_layer": [m for m in SPEC["bench"]["per_layer"]
                           if m["name"] == name]}
    assert len(bench["per_layer"]) == 1
    return harness.read_layers(bench, CELL, c).get(name)


def without(doc, *path):
    doc = copy.deepcopy(doc)
    node = doc
    for key in path[:-1]:
        node = node[key]
    del node[path[-1]]
    return doc


@pytest.mark.parametrize("name", NAMES)
def test_a_hand_made_window_gives_the_value_worked_out_by_hand(name):
    entry = next(m for m in SPEC["bench"]["per_layer"] if m["name"] == name)
    assert read(name, ctx()) == {"value": pytest.approx(WANT[name]),
                                 "unit": entry["unit"]}
    assert entry["workloads"] == [CELL] and entry["layer"] == "mask stage"
    assert entry["moves"] == "serve_imgs_per_s"


def test_mask_mfu_credits_the_records_masked_and_stays_under_the_peak():
    """Padding slots are not credited: at the cap of 100 records on all 8
    rows and the least time the head could take on the chip's peak, the
    share is 100 %; half the records in the same time, half the share."""
    net = SPEC["config"]["net"]
    full = 2 * 530059264 * 800
    assert harness.modules_of(SPEC["config"])["flops"].mask_flops_per_image(
        net, 800) == full
    least = {"modules": {"jit_mask_branch(1)": [full / 197e12]},
             "op_time": {}}
    after = copy.deepcopy(AFTER)
    after["counters"].update(mask_rois=1600 + 8000, mask_dispatches=12)
    assert read("mask_mfu", ctx(after=after, trace=least))["value"] == \
        pytest.approx(100.0)
    after["counters"]["mask_rois"] = 1600 + 4000
    assert read("mask_mfu", ctx(after=after, trace=least))["value"] == \
        pytest.approx(50.0)
    # the predict program's executions are not the mask program's
    assert read("mask_device_ms", ctx(trace={
        "modules": {"jit_fwd_wf(7)": [0.098]}, "op_time": {}})) is None


ABSENT = {
    "turn_mask_ms": [("stages", "serve/mask")],
    "turn_mask_paste_ms": [("stages", "serve/mask/paste")],
    "mask_mfu": [("counters", "mask_rois"), ("counters", "mask_dispatches")],
    "masks_per_img": [("counters", "mask_rois")],
}


@pytest.mark.parametrize("name, path", [(n, p) for n, ps in ABSENT.items()
                                        for p in ps])
def test_a_program_without_the_span_or_counter_gives_none(name, path):
    assert read(name, ctx(without(BEFORE, *path),
                          without(AFTER, *path))) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_parents_documents_and_an_empty_window_give_none(name):
    """A program from before this PR (no mask clock, no mask counter, no
    mask program in the trace) and a window in which nothing was served."""
    old = {"counters": {"served": 16, "batches": 2},
           "stages": {"serve/postprocess": {"count": 2, "sum_s": 0.1}}}
    none = {"modules": {"jit_fwd_wf(7)": [0.098]}, "op_time": {}}
    assert read(name, ctx(old, dict(old), none)) is None
    assert read(name, ctx({}, {}, {"modules": {}, "op_time": {}})) is None
    assert read(name, ctx(BEFORE, BEFORE, {"modules": {}, "op_time": {}})) \
        is None


def test_a_configuration_that_names_no_mask_program_gives_none():
    """``r101-fpn``'s file has no ``names.mask_program`` and its flops
    module no ``mask_flops_per_image``: the device readers return None."""
    fpn = harness.load_cell("fpn-serve-closed")["config"]
    assert "mask_program" not in fpn["names"]
    for name in ("mask_device_ms", "mask_mfu"):
        assert read(name, ctx(config=fpn)) is None
    # and the two patterns tell the two programs apart
    import re

    names = SPEC["config"]["names"]
    assert re.search(names["predict_program"], "jit_fwd_wf(7)")
    assert not re.search(names["predict_program"], "jit_mask_branch(123)")
    assert re.search(names["mask_program"], "jit_mask_branch(123)")
    assert not re.search(names["mask_program"], "jit_fwd_wf(7)")
