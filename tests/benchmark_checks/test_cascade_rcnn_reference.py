"""The program's RoI cascade (``FPNFasterRCNN.predict_with_feats`` +
``roi_cascade`` through ``Predictor``) against the plain reference
``benchmark/reference/cmrcnn_fpn.py`` at the tiny size, on the
configuration's seeded weights: the proposals, stage 1's probabilities and
deltas, every stage's boxes, the final boxes, the averaged probabilities,
and the mask branch over the cached pyramid.

Both sides compute in float32 here (``tpu__COMPUTE_DTYPE="float32"`` on the
program's), so what separates them is the order of summation: each
tolerance is a few float32 roundings of its quantity (a delta's 5e-5 times
a box of up to 200 px, through exp, three times over), and a stage run on
the wrong boxes, a stage skipped or a wrong clamp would miss by the stages'
own moves — several pixels.  The bfloat16 program is held to the reference
by the comparison a run uses (``test_cascade_rcnn_run.py``)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, loadgen
from benchmark.reference import cmrcnn_fpn, frcnn_c4, mrcnn_fpn
from benchmark.weights import as_tree, check_against

from . import tiny_cascade

BOXES = np.asarray([[5.2, 4.1, 30.7, 28.3], [0.0, 0.0, 116.0, 77.0],
                    [60.3, 5.5, 100.9, 70.2]], np.float32)
LABELS = np.asarray([1, 17, 80], np.int32)


@pytest.fixture(scope="module")
def both():
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.data.loader import prepare_image
    from mx_rcnn_tpu.eval.tester import Predictor
    from mx_rcnn_tpu.models.detector import build_model, init_params

    spec = tiny_cascade.tiny_spec()
    config, net = spec["config"], spec["config"]["net"]
    flat = harness.modules_of(config)["weights"].make(net, 2 ** 31 + 7)
    doc = json.loads(loadgen.make_bodies(spec["traffic"]["bodies"],
                                         2 ** 31 + 7)[0])
    ref = {}
    dense = cmrcnn_fpn.detect(flat, doc, net, stages=ref)

    cfg = generate_config(
        "resnet101_fpn_cascade_mask", "coco", tpu__SCALES=((128, 192),),
        TEST__RPN_PRE_NMS_TOP_N=500, TEST__RPN_POST_NMS_TOP_N=60,
        tpu__COMPUTE_DTYPE="float32", network__NETWORK="resnet50",
        network__PIXEL_STDS=tuple(net["pixel_stds"]))
    model = build_model(cfg)
    check_against(flat, jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0))))
    params = as_tree(flat)
    image, im_info = prepare_image(frcnn_c4.decode_body(doc), cfg,
                                   cfg.tpu.SCALES[0])
    pred = Predictor(model, params, cfg)
    out = [np.asarray(x) for x in jax.device_get(
        pred.predict(image[None], im_info[None]))]
    masks = np.asarray(jax.device_get(pred.predict_masks_cached(
        (BOXES * im_info[2])[None], LABELS[None], pred.feats_token)))[0]
    (_, _, p1, d1, _), _ = model.apply(
        {"params": params}, jnp.asarray(image[None]),
        jnp.asarray(im_info[None]), method=model.predict_with_feats)
    return ref, dense, out, masks, (np.asarray(p1)[0], np.asarray(d1)[0]), \
        float(im_info[2])


def test_the_proposals_and_stage_one(both):
    ref, _, (rois, valid, _, _, _), _, (p1, d1), _ = both
    n = len(ref["rois"])
    assert n == 60 and valid[0].all()
    np.testing.assert_allclose(rois[0], ref["rois"], atol=2e-3)
    np.testing.assert_allclose(p1, ref["probs"][0], atol=2e-5)
    # the box output is one raw delta a RoI, at stage 1's spread
    assert d1.shape == (60, 4)
    assert 0.02 < float(np.abs(d1[:, :2]).mean()) < 0.2


def test_every_stage_moves_the_boxes_and_the_program_follows(both):
    """b_2, b_3, b_4 of the reference: each stage moves the boxes (less
    and less, by the stage weights), and the program's final boxes are b_4
    — not b_2, not b_3."""
    ref, dense, (_, _, _, boxes, _), _, _, s = both
    b = ref["boxes"]
    assert len(b) == 4 and len(ref["probs"]) == 3
    moves = [float(np.abs(b[t + 1] - b[t]).mean()) for t in range(3)]
    assert moves[0] > moves[1] > moves[2] > 0.2
    np.testing.assert_allclose(boxes[0], b[3], atol=5e-3)
    for t in (1, 2):
        assert float(np.abs(boxes[0] - b[t]).max()) > 1.0
    np.testing.assert_allclose(dense["box"], b[3] / s, rtol=1e-6)
    np.testing.assert_allclose(dense["box_s2"], b[1] / s, rtol=1e-6)
    np.testing.assert_array_equal(dense["boxes"][:, 4 * 7:4 * 8],
                                  dense["box"])


def test_the_scores_are_the_mean_of_the_three_stages(both):
    ref, dense, (_, _, scores, _, _), _, _, _ = both
    want = (ref["probs"][0] + ref["probs"][1] + ref["probs"][2]) / 3
    np.testing.assert_allclose(dense["prob"], want, rtol=1e-6)
    np.testing.assert_allclose(scores[0], want, atol=2e-5)
    # each stage scores apart from the others: the mean is no one stage's
    for t in range(3):
        assert float(np.abs(want - ref["probs"][t]).max()) > 0.05


def test_the_mask_branch_over_the_cached_pyramid(both):
    _, dense, _, masks, _, _ = both
    want = mrcnn_fpn.mask_probs(dense, BOXES, LABELS,
                                tiny_cascade.tiny_spec()["config"]["net"])
    assert masks.shape == want.shape == (3, 28, 28)
    np.testing.assert_allclose(masks, want, atol=2e-5)


def test_a_stage_run_twice_is_another_answer(both):
    """The reference's own stages in another order (stage 2's head twice)
    give other boxes and scores — what the comparison's gaps then see."""
    ref, _, _, _, _, _ = both
    spec = tiny_cascade.tiny_spec()
    net = spec["config"]["net"]
    flat = harness.modules_of(spec["config"])["weights"].make(net,
                                                              2 ** 31 + 7)
    eh, ew, _ = ref["im_info"]
    twice = cmrcnn_fpn.run_stages(flat, ref["feats"], ref["rois"], eh, ew,
                                  net, order=(1, 2, 2))
    np.testing.assert_allclose(twice["boxes"][2], ref["boxes"][2], atol=1e-4)
    assert float(np.abs(twice["boxes"][3] - ref["boxes"][3]).max()) > 1.0
    assert float(np.abs(twice["probs"][2] - ref["probs"][2]).max()) > 0.05
