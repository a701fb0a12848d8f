"""``r101-fpn-cascade-mask``'s comparison as a whole run on a tiny server (the
pattern of ``test_mask_run.py``): the program's own ``serve.py`` with the
configuration's network and flags at the tiny size, the cell's closed-loop
traffic, the plain reference over the sampled requests, the
configuration's comparison and limits; the counter this PR adds and the
programs a turn runs; stand-ins that skip a stage, run one twice or answer
stage 1's scores, which the comparison has to refuse; and the float8
control, which has to read not correct by a gap that rounding moves.

CPU readings at this size (seed 2**31 + 3; stand-ins and the control on
seeds 1-2): the program's ``box_gap`` 0.0062, ``score_gap`` 0.0059,
``stage1_box_iou`` 0.833, ``stage1_score_gap`` 0.666; skipping stages 2-3
reads 1.0 and 0.0, stage 2 twice ``box_gap`` 0.22 and ``score_gap`` 0.51;
the float8 control ``box_gap`` 0.155-0.263, ``mask_gap`` 0.30-0.33 and the
two stage numbers as the program's (0.83-0.84, 0.62-0.66)."""

import json
import time

import pytest

from benchmark import harness, loadgen
from benchmark.drivers import serve as drv
from benchmark.mask.control import with_masks
from benchmark.reference import cmrcnn_fpn
from benchmark.reference.frcnn_c4 import records

from . import tiny_cascade

SEED = 2 ** 31 + 3
GAPS = ("box_gap", "score_gap", "mask_gap", "mask_firm_faults")


@pytest.fixture(scope="module")
def sound_run():
    spec = tiny_cascade.tiny_spec()
    seen = {}
    real = drv.serve_window

    def keeping(*a, **kw):
        seen.update(real(*a, **kw))
        return seen

    drv.serve_window = keeping
    try:
        line, compared = drv.run(spec, SEED, 6.0, False,
                                 harness.device_doc(), time.monotonic())
    finally:
        drv.serve_window = real
    return spec, json.loads(line), compared, seen


def test_a_sound_run_of_the_cascade_is_correct(sound_run):
    spec, doc, _, out = sound_run
    assert doc["correct"] is True, doc["compared"]
    assert doc["attempted"] > 8 and doc["failed"] == 0
    assert set(doc["metrics"]) == {"setup_s", "serve_imgs_per_s"}
    assert set(doc["compared"]) == set(spec["config"]["correct"])
    c = {k: v["value"] for k, v in doc["compared"].items()}
    assert c["masks"] == c["records"] >= 40
    assert c["records"] == 12 * len(out["result"]["sample"])
    assert c["box_gap"] < 0.012 and c["score_gap"] < 0.03
    assert c["stage1_box_iou"] < 0.9 and c["stage1_score_gap"] > 0.4
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
    assert argv[:2] == ["--network", "resnet101_fpn_cascade_mask"]
    assert "--serve-e2e" not in argv


def test_every_batch_ran_the_stages_and_nothing_compiled(sound_run):
    """``roi_cascade_batches`` counts each batch of the window; three
    programs an orientation were warmed (predict, the stages, the mask
    branch) and none built after; the stage clocks of the mask stage run
    as on the mask network."""
    spec, _, _, out = sound_run
    before, after = out["metrics_before"], out["metrics_after"]
    batches = after["counters"]["batches"] - before["counters"]["batches"]
    assert batches > 0
    assert after["counters"]["roi_cascade_batches"] \
        - before["counters"]["roi_cascade_batches"] == batches
    assert after["counters"]["recompiles"] == \
        after["counters"]["warmup_programs"] == 6
    assert after["counters"]["mask_dispatches"] \
        - before["counters"]["mask_dispatches"] == batches
    ctx = {"metrics_before": before, "metrics_after": after,
           "config": spec["config"], "trace": {"modules": {}, "op_time": {}},
           "peaks": harness.peaks_for("TPU v5 lite"),
           "flops": harness.modules_of(spec["config"])["flops"]}
    mine = ("recompiles_in_window", "xla_compiles_in_window",
            "roi_cascade_device_ms", "roi_cascade_mfu")
    got = harness.read_layers(
        {"per_layer": [m for m in spec["bench"]["per_layer"]
                       if m["name"] in mine]}, "cascade-serve-closed", ctx)
    assert got == {"recompiles_in_window": {"value": 0.0, "unit": "count"},
                   "xla_compiles_in_window": {"value": 0.0,
                                              "unit": "count"}}


def stand_in(spec, seed, order=None, scores="prob", bodies=2):
    """The comparison's numbers with the float32 reference in the
    program's place, its stages run in ``order``, its records scored by
    ``scores`` (``prob``: the stages' mean; ``prob_s1``: stage 1's)."""
    config, net = spec["config"], spec["config"]["net"]
    mods = harness.modules_of(config)
    flat = mods["weights"].make(net, seed)
    pool = loadgen.make_bodies(spec["traffic"]["bodies"], seed)
    sample, dense = [], []
    for body in pool[:bodies]:
        doc = json.loads(body)
        dense.append(cmrcnn_fpn.detect(flat, doc, net))
        low = cmrcnn_fpn.detect(flat, doc, net, order=order)
        recs = records(low[scores], low["boxes"], net["num_classes"],
                       net["test_thresh"], net["test_nms"],
                       net["test_max_per_image"])
        sample.append({"doc": doc, "detections": with_masks(recs, low, net)})
    numbers = mods["compare"].compare(sample, dense, net)
    limits = dict(config["correct"], records=20, masks=20)
    return numbers, mods["compare"].judge(numbers, limits)


def test_the_reference_in_the_programs_place_is_correct():
    numbers, (ok, _) = stand_in(tiny_cascade.tiny_spec(), 1)
    assert ok is True
    assert numbers["box_gap"] == numbers["score_gap"] == 0.0


@pytest.mark.parametrize("fault", ["skips_stages", "stage2_twice",
                                   "stage1_scores"])
def test_a_program_that_skips_or_repeats_a_stage_is_not_correct(fault):
    spec = tiny_cascade.tiny_spec()
    limits = spec["config"]["correct"]
    numbers, (ok, compared) = stand_in(spec, 1, **{
        "skips_stages": {"order": (1,)},
        "stage2_twice": {"order": (1, 2, 2)},
        "stage1_scores": {"scores": "prob_s1"}}[fault])
    assert ok is False
    if fault == "skips_stages":
        # stage 1's boxes b_2 and scores: both numbers on the stages catch it
        assert numbers["stage1_box_iou"] > limits["stage1_box_iou"]
        assert numbers["stage1_score_gap"] < limits["stage1_score_gap"]
    elif fault == "stage2_twice":
        assert numbers["box_gap"] > limits["box_gap"]
        assert numbers["score_gap"] > limits["score_gap"]
    else:
        # the final boxes, stage 1's scores alone
        assert numbers["box_gap"] < limits["box_gap"]
        assert numbers["stage1_score_gap"] < limits["stage1_score_gap"]


@pytest.mark.parametrize("seed", [1, 2])
def test_the_float8_control_of_the_cascade_is_not_correct(seed):
    spec = tiny_cascade.tiny_spec()
    mods = harness.modules_of(spec["config"])
    numbers = mods["control"].control_numbers(spec["config"], spec["traffic"],
                                              seed, bodies=4)
    ok, compared = mods["compare"].judge(numbers, spec["config"]["correct"])
    assert ok is False
    assert [k for k in GAPS if compared[k][0] > compared[k][1]], compared
    # it runs every stage: the two numbers on the stages pass
    limits = spec["config"]["correct"]
    assert numbers["stage1_box_iou"] < limits["stage1_box_iou"]
    assert numbers["stage1_score_gap"] > limits["stage1_score_gap"]
    assert numbers["mask_missing"] == 0 and numbers["masks"] == 60
