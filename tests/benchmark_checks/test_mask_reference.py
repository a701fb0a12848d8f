"""The program's mask branch (``FPNFasterRCNN.masks_from_feats`` through
``Predictor``) and its paste against the plain reference
``benchmark/reference/mrcnn_fpn.py`` at the tiny size, on seeded random
weights.

Both sides compute in float32 here (``tpu__COMPUTE_DTYPE="float32"`` on the
program's), so what separates them is the order of summation: the
tolerances below are a few float32 roundings of a probability, and a wrong
level, tap, deconv phase or class channel would miss by the spread itself.
The bfloat16 program is held to the reference by the comparison a run uses
(``test_mask_run.py``)."""

import json

import jax
import numpy as np
import pytest

from benchmark import harness, loadgen
from benchmark.reference import frcnn_c4, mrcnn_fpn
from benchmark.weights import as_tree, check_against

from . import tiny_mask

# original-frame boxes on a 78 x 117 body resized 1.6x: small, large, thin,
# over the edge; classes spread over the 81 channels
BOXES = np.asarray([[5.2, 4.1, 30.7, 28.3], [0.0, 0.0, 116.0, 77.0],
                    [40.5, 10.0, 41.2, 60.0], [90.0, 50.0, 130.0, 90.0],
                    [10.0, 30.0, 80.0, 40.0], [60.3, 5.5, 100.9, 70.2]],
                   np.float32)
LABELS = np.asarray([1, 17, 80, 44, 3, 62], np.int32)


@pytest.fixture(scope="module")
def both():
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.data.loader import prepare_image
    from mx_rcnn_tpu.eval.tester import Predictor
    from mx_rcnn_tpu.models.detector import build_model, init_params

    spec = tiny_mask.tiny_spec()
    config, net = spec["config"], spec["config"]["net"]
    flat = harness.modules_of(config)["weights"].make(net, 2 ** 31 + 7)
    doc = json.loads(loadgen.make_bodies(spec["traffic"]["bodies"],
                                         2 ** 31 + 7)[0])
    dense = mrcnn_fpn.detect(flat, doc, net)

    cfg = generate_config(
        "resnet101_fpn_mask", "coco", tpu__SCALES=((128, 192),),
        TEST__RPN_PRE_NMS_TOP_N=500, TEST__RPN_POST_NMS_TOP_N=60,
        tpu__COMPUTE_DTYPE="float32", network__NETWORK="resnet50",
        network__PIXEL_STDS=tuple(net["pixel_stds"]))
    model = build_model(cfg)
    check_against(flat, jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0))))
    pred = Predictor(model, as_tree(flat), cfg)
    image, im_info = prepare_image(frcnn_c4.decode_body(doc), cfg,
                                   cfg.tpu.SCALES[0])
    pred.predict(image[None], im_info[None])
    probs = np.asarray(jax.device_get(pred.predict_masks_cached(
        (BOXES * im_info[2])[None], LABELS[None], pred.feats_token)))[0]
    return net, dense, probs, float(im_info[2])


def test_detect_keeps_what_the_box_reference_gives_and_the_pyramid(both):
    net, dense, _, scale = both
    assert dense["prob"].shape == (60, 81)
    assert dense["boxes"].shape == (60, 324)
    assert [tuple(f.shape) for f in dense["feats"]] == [
        (32, 48, 256), (16, 24, 256), (8, 12, 256), (4, 6, 256)]
    assert all(f.dtype == np.float32 for f in dense["feats"])
    assert dense["scale"] == pytest.approx(scale, rel=1e-6)
    assert sorted(dense["params"]) == sorted(
        f"mask_head/{n}/{leaf}" for n in ("mask_conv1", "mask_conv2",
                                          "mask_conv3", "mask_conv4",
                                          "mask_deconv", "mask_out")
        for leaf in ("kernel", "bias"))


def test_mask_probabilities_at_given_boxes(both):
    """28 x 28 maps of six boxes on P2..P4: probabilities to 2e-4 (logits
    spread by a few units after six layers); the maps are decisive (most
    cells further than 0.1 from the cut) and differ from box to box."""
    net, dense, probs, _ = both
    want = mrcnn_fpn.mask_probs(dense, BOXES, LABELS, net)
    assert probs.shape == want.shape == (6, 28, 28)
    np.testing.assert_allclose(probs, want, atol=2e-4)
    assert np.mean(np.abs(want - 0.5) > 0.1) > 0.5
    assert 0.15 < np.mean(want >= 0.5) < 0.85
    assert not np.allclose(want[0], want[4], atol=0.05)
    # another class reads another channel: not the same map
    other = mrcnn_fpn.mask_probs(dense, BOXES[:1], [2], net)
    assert not np.allclose(other[0], want[0], atol=0.05)


def test_the_levels_the_boxes_pool_from(both):
    """Eq. 1 on the scaled boxes: the small ones on P2, the whole frame
    higher; the thin box is held to a cell's width by RoIAlign's floor."""
    from benchmark.reference import frcnn_fpn

    net, dense, _, scale = both
    levels = frcnn_fpn.assign_level(BOXES * np.float32(scale))
    assert levels.tolist() == [2, 3, 2, 2, 2, 2]


FRAMES = {
    "inside": ([5.2, 4.1, 30.7, 28.3], 78, 117),
    "over-the-right-and-bottom-edge": ([90.0, 50.0, 130.0, 90.0], 78, 117),
    "over-the-left-and-top-edge": ([-12.5, -7.0, 20.0, 15.5], 78, 117),
    "a-pixel-wide": ([40.0, 10.0, 40.0, 60.0], 78, 117),
    "a-pixel-in-all": ([7.3, 9.9, 7.4, 9.95], 78, 117),
    "larger-than-the-frame": ([-30.0, -20.0, 200.0, 120.0], 78, 117),
    "wholly-outside": ([150.0, 10.0, 170.0, 30.0], 78, 117),
    "smaller-than-the-map": ([10.0, 10.0, 19.0, 17.0], 78, 117),
}


@pytest.mark.parametrize("name", sorted(FRAMES))
def test_the_paste_is_the_programs(name):
    """The reference's pasted probabilities, cut at 0.5, against
    ``eval.tester.paste_mask`` (cv2) and the native paste: the same window,
    and the same bit wherever the probability is further than 1e-5 from the
    cut."""
    from mx_rcnn_tpu import native
    from mx_rcnn_tpu.eval.mask_rle import decode
    from mx_rcnn_tpu.eval.tester import paste_mask

    box, h, w = FRAMES[name]
    box = np.asarray(box, np.float32)
    rng = np.random.default_rng(len(name))
    prob = rng.random((28, 28), dtype=np.float32)
    (x0, y0), pasted = mrcnn_fpn.paste(prob, box, h, w)
    assert pasted.dtype == np.float64
    full = np.zeros((h, w))
    full[y0:y0 + pasted.shape[0], x0:x0 + pasted.shape[1]] = pasted
    firm = np.ones((h, w), bool)
    firm[y0:y0 + pasted.shape[0], x0:x0 + pasted.shape[1]] = \
        np.abs(pasted - 0.5) > 1e-5
    theirs = paste_mask(prob, box, h, w).astype(bool)
    assert ((full >= 0.5) == theirs)[firm].all()
    if name == "wholly-outside":
        assert pasted.size == 0 and not theirs.any()
    else:
        assert pasted.size > 0 and theirs.any()
    counts = native.paste_rle(prob, box, h, w)
    if counts is not None:
        fast = decode({"size": [h, w], "counts": counts}).astype(bool)
        assert ((full >= 0.5) == fast)[firm].all()
