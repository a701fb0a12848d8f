"""``r101-fpn``'s comparison as a whole run on a tiny server (the pattern of
``test_reference.py``): the program's own ``serve.py`` with the
configuration's network and flags at the tiny size, the cell's closed-loop
traffic, the plain reference over the sampled requests, the configuration's
comparison and limits; an answer altered where it is produced; the float8
control, which has to read not correct; and the counters this PR adds, on
``/metrics`` and through their readers."""

import json
import time

import pytest

from benchmark import harness
from benchmark.drivers import serve as drv

from . import tiny_fpn


@pytest.fixture(scope="module")
def sound_run():
    """One run; ``serve_window``'s findings are kept beside the result."""
    spec = tiny_fpn.tiny_spec()
    seen = {}
    real = drv.serve_window

    def keeping(*a, **kw):
        seen.update(real(*a, **kw))
        return seen

    drv.serve_window = keeping
    try:
        line, compared = drv.run(spec, 2 ** 31 + 3, 3.0, False,
                                 harness.device_doc(), time.monotonic())
    finally:
        drv.serve_window = real
    return spec, json.loads(line), compared, seen


def test_a_sound_run_of_the_pyramid_detector_is_correct(sound_run):
    spec, doc, compared, _ = sound_run
    assert doc["correct"] is True, doc["compared"]
    assert doc["attempted"] > 8 and doc["failed"] == 0
    names = {m["name"] for m in harness.metrics_of(
        spec["bench"], "end_to_end", "fpn-serve-closed")}
    assert set(doc["metrics"]) == names == {"setup_s", "serve_imgs_per_s"}
    assert doc["metrics"]["serve_imgs_per_s"]["value"] > 0
    assert set(doc["compared"]) == set(spec["config"]["correct"])
    assert doc["compared"]["box_gap"]["value"] < 0.015
    assert doc["compared"]["score_gap"]["value"] < 0.06
    assert doc["compared"]["records"]["value"] >= 100


def test_the_run_went_through_the_configurations_own_modules(sound_run):
    spec, _, _, _ = sound_run
    mods = harness.modules_of(spec["config"])
    assert {k: m.__name__ for k, m in mods.items()} == {
        "weights": "benchmark.fpn.weights",
        "reference": "benchmark.reference.frcnn_fpn",
        "compare": "benchmark.compare", "flops": "benchmark.fpn.flops",
        "control": "benchmark.fpn.control"}
    argv = drv.server_argv(spec["config"], "s.sock")
    assert argv[:2] == ["--network", "resnet50_fpn"]
    assert "--serve-batch" in argv and "--serve-e2e" not in argv
    # its programs get a marker of their own, not r101-c4's
    assert harness.programs_marker(spec) != harness.programs_marker(
        harness.load_cell("c4-serve-closed"))


def test_the_roi_counters_are_on_metrics_and_their_readers_read(sound_run):
    """``rois_valid`` and ``rois_level_p2`` .. ``p5`` beside
    ``post_candidates``, cumulative; the levels sum to the valid count; the
    two readers give a number from the window's difference."""
    spec, _, _, out = sound_run
    before = out["metrics_before"]["counters"]
    after = out["metrics_after"]["counters"]
    levels = [f"rois_level_p{i}" for i in (2, 3, 4, 5)]
    for name in ["rois_valid", "post_candidates"] + levels:
        assert after[name] >= before[name] >= 0
    assert before["rois_valid"] > 0          # the warm requests were counted
    assert sum(after[k] for k in levels) == after["rois_valid"]
    served = after["served"] - before["served"]
    assert served > 0
    ctx = {"metrics_before": out["metrics_before"],
           "metrics_after": out["metrics_after"]}
    got = harness.read_layers(
        {"per_layer": [m for m in spec["bench"]["per_layer"]
                       if m["name"] in ("rois_valid_per_img",
                                        "roi_p2_share")]},
        "fpn-serve-closed", ctx)
    per_img = got["rois_valid_per_img"]["value"]
    assert per_img == (after["rois_valid"] - before["rois_valid"]) / served
    assert 1 <= per_img <= 60               # TEST__RPN_POST_NMS_TOP_N here
    assert 0 < got["roi_p2_share"]["value"] <= 100
    assert got["roi_p2_share"]["unit"] == "%"


def test_an_altered_answer_of_the_pyramid_detector_is_not_correct(
        monkeypatch):
    """Every box of every record moved by a fifth of its width, as a wrong
    scale in the un-resize would: well-formed, on time — and wrong."""
    from mx_rcnn_tpu.serve import engine

    real = engine.detections_to_records

    def altered(dets):
        recs = real(dets)
        for r in recs:
            x1, y1, x2, y2 = r["bbox"]
            d = 0.2 * (x2 - x1 + 1.0)
            r["bbox"] = [x1 + d, y1, x2 + d, y2]
        return recs

    monkeypatch.setattr(engine, "detections_to_records", altered)
    line, _ = drv.run(tiny_fpn.tiny_spec(), 2 ** 31 + 3, 3.0, False,
                      harness.device_doc(), time.monotonic())
    doc = json.loads(line)
    assert doc["correct"] is False
    assert doc["compared"]["box_gap"]["value"] > 0.015
    assert doc["failed"] == 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_of_the_pyramid_detector_is_not_correct(seed):
    spec = tiny_fpn.tiny_spec()
    mods = harness.modules_of(spec["config"])
    numbers = mods["control"].control_numbers(spec["config"], spec["traffic"],
                                              seed, bodies=4)
    ok, compared = mods["compare"].judge(numbers, spec["config"]["correct"])
    assert ok is False
    failing = [k for k, (v, lim) in compared.items()
               if k != "records" and v > lim]
    assert failing, compared
    # the same pipeline in float32 is the reference itself: all zeros
    exact = mods["control"].control_numbers(
        spec["config"], spec["traffic"], seed, bodies=2, precision="f32")
    assert exact["box_gap"] == 0.0 and exact["score_gap"] == 0.0
    assert mods["compare"].judge(exact, spec["config"]["correct"])[0] is True
