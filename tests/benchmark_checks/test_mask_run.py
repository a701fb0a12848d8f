"""``r101-fpn-mask``'s comparison as a whole run on a tiny server (the
pattern of ``test_fpn_run.py``): the program's own ``serve.py`` with the
configuration's network and flags at the tiny size, the cell's closed-loop
traffic, the plain reference over the sampled requests with its mask branch
run at the served boxes, the configuration's comparison and limits; the
driver's half after the window on the same window with the masks altered;
the float8 control, which has to read not correct by a mask limit; and the
stage clocks and counters this PR adds, on ``/metrics`` and through their
readers.

CPU readings at this size (seeds 2**31 + 3 and + 5; float8 control, seeds
1-3): ``mask_gap`` 0.046 against 0.31-0.33, ``mask_firm_faults`` at a
margin of 0.02: 3e-5 against 0.051-0.054; ``mask_fill`` 0.29-0.32."""

import copy
import json
import time

import pytest

from benchmark import harness
from benchmark.drivers import serve as drv

from . import tiny_mask

SEED = 2 ** 31 + 3
MASK_NUMBERS = ("mask_missing", "mask_gap", "mask_firm_faults")


@pytest.fixture(scope="module")
def sound_run():
    """One run; ``serve_window``'s findings are kept beside the result."""
    spec = tiny_mask.tiny_spec()
    seen = {}
    real = drv.serve_window

    def keeping(*a, **kw):
        seen.update(real(*a, **kw))
        return seen

    drv.serve_window = keeping
    try:
        line, compared = drv.run(spec, SEED, 3.0, False,
                                 harness.device_doc(), time.monotonic())
    finally:
        drv.serve_window = real
    return spec, json.loads(line), compared, seen


def test_a_sound_run_of_the_mask_detector_is_correct(sound_run):
    spec, doc, compared, out = sound_run
    assert doc["correct"] is True, doc["compared"]
    assert doc["attempted"] > 8 and doc["failed"] == 0
    assert set(doc["metrics"]) == {"setup_s", "serve_imgs_per_s"}
    assert set(doc["compared"]) == set(spec["config"]["correct"])
    c = {k: v["value"] for k, v in doc["compared"].items()}
    assert c["masks"] == c["records"] >= 40       # a mask on every record
    assert c["mask_missing"] == 0 and c["mask_firm_faults"] < 5e-4
    assert c["mask_gap"] < 0.09 and c["box_gap"] < 0.015
    assert 0.15 < c["mask_fill"] < 0.8
    # every sampled response: 12 records, each with its mask in its frame
    for s in out["result"]["sample"]:
        h, w = s["doc"]["shape"][:2]
        assert len(s["detections"]) == 12
        assert all(r["segmentation"]["size"] == [h, w]
                   for r in s["response"]["detections"])


def test_the_run_went_through_the_configurations_own_modules(sound_run):
    spec, _, _, _ = sound_run
    mods = harness.modules_of(spec["config"])
    assert {k: m.__name__ for k, m in mods.items()} == \
        spec["config"]["modules"]
    argv = drv.server_argv(spec["config"], "s.sock")
    assert argv[:2] == ["--network", "resnet101_fpn_mask"]
    assert "--serve-e2e" not in argv
    assert set(argv) - set(drv.server_argv(
        harness.load_cell("fpn-serve-closed")["config"], "s.sock")) == {
        "resnet101_fpn_mask", "network__NETWORK='resnet50'",
        "TEST__MAX_PER_IMAGE=12", "tpu__SCALES=((128,192),)",
        "TEST__RPN_PRE_NMS_TOP_N=500", "TEST__RPN_POST_NMS_TOP_N=60", "2"}


def test_the_mask_clocks_and_counters_are_on_metrics_and_their_readers_read(
        sound_run):
    spec, _, _, out = sound_run
    before, after = out["metrics_before"], out["metrics_after"]
    for name in ("mask_dispatches", "mask_rois", "mask_readback_bytes",
                 "mask_native"):
        assert after["counters"][name] >= before["counters"][name] >= 0
    batches = after["counters"]["batches"] - before["counters"]["batches"]
    assert batches > 0
    assert after["counters"]["mask_dispatches"] \
        - before["counters"]["mask_dispatches"] == batches
    for name in ("serve/mask", "serve/mask/forward", "serve/mask/readback",
                 "serve/mask/paste"):
        assert after["stages"][name]["count"] \
            - before["stages"][name]["count"] == batches
    ctx = {"metrics_before": before, "metrics_after": after,
           "config": spec["config"], "trace": {"modules": {}, "op_time": {}},
           "peaks": harness.peaks_for("TPU v5 lite"),
           "flops": harness.modules_of(spec["config"])["flops"]}
    mine = ("turn_mask_ms", "turn_mask_paste_ms", "mask_device_ms",
            "mask_mfu", "masks_per_img", "turn_ms", "turn_postprocess_ms",
            "recompiles_in_window", "xla_compiles_in_window")
    got = {k: v["value"] for k, v in harness.read_layers(
        {"per_layer": [m for m in spec["bench"]["per_layer"]
                       if m["name"] in mine]},
        "mask-serve-closed", ctx).items()}
    assert set(got) == set(mine) - {"mask_device_ms", "mask_mfu"}  # no trace
    assert got["masks_per_img"] == 12.0
    assert 0 < got["turn_mask_paste_ms"] < got["turn_mask_ms"]
    assert got["turn_mask_ms"] + got["turn_postprocess_ms"] < got["turn_ms"]
    assert got["recompiles_in_window"] == 0
    assert got["xla_compiles_in_window"] == 0
    assert after["counters"]["recompiles"] == \
        after["counters"]["warmup_programs"] == 4


@pytest.mark.parametrize("fault", ["none", "swapped", "dropped"])
def test_the_drivers_half_after_the_window_on_the_cells_files(sound_run,
                                                              fault):
    """Reference -> compare -> judge -> last line, on the sound run's own
    window: as it was; with every record answering its neighbour's mask
    (well-formed, on time — and another box's); with one mask dropped."""
    spec, doc, _, out = sound_run
    config = spec["config"]
    mods = harness.modules_of(config)
    flat = mods["weights"].make(config["net"], SEED)
    res = copy.deepcopy(out["result"])
    for s in res["sample"]:
        recs = s["response"]["detections"]
        segs = [r["segmentation"] for r in recs]
        if fault == "swapped":
            for r, seg in zip(recs, segs[1:] + segs[:1]):
                r["segmentation"] = seg
        elif fault == "dropped":
            del recs[0]["segmentation"]
        s["detections"] = recs
    line, compared = drv.after_window(
        mods, config, flat, res, {"setup_s": {"value": 1.0, "unit": "s"}},
        {"platform": "cpu", "kind": "cpu", "count": 1})
    got = json.loads(line)
    assert list(got)[-1] == "compared"
    for name in ("box_gap", "score_gap", "records"):
        assert got["compared"][name] == doc["compared"][name]
    if fault == "none":
        assert got["correct"] is True
        assert got["compared"] == doc["compared"]
    elif fault == "swapped":
        assert got["correct"] is False
        assert got["compared"]["mask_missing"]["value"] > 0    # off-window
    else:
        assert got["correct"] is False
        assert compared["mask_missing"] == (float(len(res["sample"])), 0)


@pytest.mark.parametrize("seed", [1, 2])
def test_the_float8_control_of_the_mask_detector_is_not_correct(seed):
    spec = tiny_mask.tiny_spec()
    mods = harness.modules_of(spec["config"])
    numbers = mods["control"].control_numbers(spec["config"], spec["traffic"],
                                              seed, bodies=4)
    ok, compared = mods["compare"].judge(numbers, spec["config"]["correct"])
    assert ok is False
    failing = [k for k in MASK_NUMBERS if compared[k][0] > compared[k][1]]
    assert failing, compared               # by a mask limit, not a box one
    assert numbers["mask_missing"] == 0 and numbers["masks"] == 60
    if seed == 1:
        # the same pipeline in float32 is the reference itself: all zeros
        exact = mods["control"].control_numbers(
            spec["config"], spec["traffic"], seed, bodies=2,
            precision="f32")
        assert exact["mask_gap"] == exact["mask_firm_faults"] == 0.0
        assert exact["box_gap"] == 0.0
        limits = dict(spec["config"]["correct"], records=20, masks=20)
        assert mods["compare"].judge(exact, limits)[0] is True
