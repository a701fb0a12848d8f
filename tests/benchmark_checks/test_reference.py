"""The plain reference against the program at the tiny test size, the
control that has to come out as not correct, and a run with the timed path
broken underneath."""

import json
import time

import numpy as np
import pytest

from benchmark import compare, control, harness, weights
from benchmark.drivers import serve as drv
from benchmark.reference import frcnn_c4

from . import tiny


def test_resize_is_cv2s_bilinear():
    import cv2

    rng = np.random.default_rng(0)
    for h, w, s in ((37, 53, 1.7), (80, 60, 1.266), (64, 96, 0.5)):
        im = rng.random((h, w, 3), dtype=np.float32)
        want = cv2.resize(im, None, None, fx=s, fy=s,
                          interpolation=cv2.INTER_LINEAR)
        got = frcnn_c4.resize_bilinear(im, s)
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=2e-6)


def test_anchors_are_the_published_nine():
    a = frcnn_c4.base_anchors()
    assert a.shape == (9, 4)
    np.testing.assert_allclose(a[0], [-84, -40, 99, 55])      # 0.5 x 8
    np.testing.assert_allclose(a[4], [-120, -120, 135, 135])  # 1 x 16
    g = frcnn_c4.grid_anchors(2, 3)
    assert g.shape == (54, 4)
    np.testing.assert_allclose(g[9 + 4], a[4] + [16, 0, 16, 0])


def test_greedy_nms_by_hand():
    boxes = np.array([[0, 0, 9, 9], [1, 1, 10, 10], [20, 20, 29, 29],
                      [0, 0, 9, 8]], np.float32)
    scores = np.array([0.9, 0.8, 0.7, 0.6], np.float32)
    assert list(frcnn_c4.greedy_nms(boxes, scores, 0.5)) == [0, 2]
    assert list(frcnn_c4.greedy_nms(boxes, scores, 0.95)) == [0, 1, 2, 3]
    assert list(frcnn_c4.greedy_nms(boxes, scores, 0.5, max_out=1)) == [0]


def test_weights_depend_on_the_seed_alone_and_seeds_may_pass_2_31():
    net = tiny.tiny_spec()["config"]["net"]
    a = weights.make(net, 2 ** 31 + 12)
    b = weights.make(net, 2 ** 31 + 12)
    c = weights.make(net, 12)
    k = "backbone/stage2/unit1/conv2/kernel"
    assert np.array_equal(a[k], b[k]) and not np.array_equal(a[k], c[k])
    tree = weights.as_tree(a)
    assert tree["rpn"]["rpn_cls_score"]["bias"].shape == (18,)
    with pytest.raises(RuntimeError):
        weights.check_against(a, {"backbone": {"conv1": {"kernel": a[k]}}})


def test_every_seed_is_the_same_network_in_another_order():
    """The seed permutes hidden channels: other arrays, the same multiset of
    values, the same function up to the order of summation — so the work a
    request costs does not depend on the seed."""
    import jax.numpy as jnp

    net = tiny.tiny_spec()["config"]["net"]
    a, b = weights.make(net, 1), weights.make(net, 2 ** 31 + 9)
    for k in ("backbone/stage1/unit2/conv1/kernel", "rpn/rpn_conv_3x3/bias",
              "head_body/stage4/unit3/bn2/gamma"):
        assert not np.array_equal(a[k], b[k])
        np.testing.assert_array_equal(np.sort(np.ravel(a[k])),
                                      np.sort(np.ravel(b[k])))
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(1, 96, 128, 3)).astype(np.float32))
    fa, la, da = frcnn_c4.trunk_and_rpn(a, x, depth="resnet50")
    fb, lb, db = frcnn_c4.trunk_and_rpn(b, x, depth="resnet50")
    np.testing.assert_allclose(fa, fb, atol=1e-3)
    np.testing.assert_allclose(la, lb, atol=1e-3)
    np.testing.assert_allclose(da, db, atol=1e-4)


@pytest.fixture(scope="module")
def sound_run():
    spec = tiny.tiny_spec()
    line, compared = drv.run(spec, 2 ** 31 + 3, 3.0, False,
                             harness.device_doc(), time.monotonic())
    return spec, json.loads(line), compared


def test_a_sound_run_is_correct_and_prints_the_contracts_line(sound_run):
    spec, doc, compared = sound_run
    assert list(doc)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(doc)[-1] == "compared"
    assert doc["correct"] is True, doc["compared"]
    assert doc["attempted"] == 12 and doc["failed"] == 0
    names = {m["name"] for m in harness.metrics_of(
        spec["bench"], "end_to_end", spec["cell"]["name"])}
    assert set(doc["metrics"]) == names
    assert all(v["value"] > 0 for v in doc["metrics"].values())
    assert doc["metrics"]["serve_imgs_per_s"]["value"] == 4.0
    assert set(doc["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    # each number compared stands beside its limit
    assert set(doc["compared"]) == set(spec["config"]["correct"])
    assert doc["compared"]["box_gap"]["value"] < 0.009
    assert doc["compared"]["records"]["value"] >= 100


def test_an_altered_answer_is_not_correct(monkeypatch):
    """The fault a serving cell can have: answers altered where they are
    produced (every box of every record moved by a fifth of its width, as a
    wrong scale in the un-resize would)."""
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
    line, _ = drv.run(tiny.tiny_spec(), 2 ** 31 + 3, 3.0, False,
                      harness.device_doc(), time.monotonic())
    doc = json.loads(line)
    assert doc["correct"] is False
    assert doc["compared"]["box_gap"]["value"] > 0.009
    assert doc["failed"] == 0        # well-formed, on time — and wrong


def test_scores_of_another_request_are_not_correct(monkeypatch):
    """The other way an answer is altered: a row mix-up in the batch, every
    request answered with the records of the first of its batch."""
    from mx_rcnn_tpu.serve import engine

    real = engine.per_class_nms
    first = {}

    def mixed(scores, boxes, valid, *a, **kw):
        if not first:
            first["scores"], first["boxes"], first["valid"] = (scores, boxes,
                                                               valid)
        return real(first["scores"], first["boxes"], first["valid"], *a, **kw)

    monkeypatch.setattr(engine, "per_class_nms", mixed)
    line, _ = drv.run(tiny.tiny_spec(), 2 ** 31 + 3, 3.0, False,
                      harness.device_doc(), time.monotonic())
    doc = json.loads(line)
    assert doc["correct"] is False


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_is_not_correct(seed):
    spec = tiny.tiny_spec()
    numbers = control.control_numbers(spec["config"], spec["traffic"], seed,
                                      bodies=4)
    ok, compared = compare.judge(numbers, spec["config"]["correct"])
    assert ok is False
    failing = [k for k, (v, lim) in compared.items()
               if k != "records" and v > lim]
    assert failing, compared
    # and the same pipeline in float32 is the reference itself: all zeros
    exact = control.control_numbers(spec["config"], spec["traffic"], seed,
                                    bodies=2, precision="f32")
    assert exact["box_gap"] == 0.0 and exact["score_gap"] == 0.0
    assert compare.judge(exact, spec["config"]["correct"])[0] is True


def test_structure_faults_are_counted():
    recs = [{"cls": 1, "score": 0.9, "bbox": [0.0, 0.0, 9.0, 9.0]},
            {"cls": 1, "score": 0.95, "bbox": [0.0, 0.0, 9.0, 8.0]},
            {"cls": 2, "score": 0.0005, "bbox": [0.0, 0.0, 9.0, 9.0]}]
    assert compare.structure_faults(recs, 0.3, 1e-3) == (1, 1, 1)
    ok = [recs[0], {"cls": 2, "score": 0.5, "bbox": [0.0, 0.0, 9.0, 9.0]}]
    assert compare.structure_faults(ok, 0.3, 1e-3) == (0, 0, 0)
    assert compare.judge({"records": 3.0, "box_gap": 0.5},
                         {"records": 10, "box_gap": 1.0})[0] is False
