"""The program (``FPNFasterRCNN`` on the ViT trunk, through ``Predictor``)
against the plain reference ``benchmark/reference/mrcnn_vitdet.py`` stage by
stage at the tiny size, on seeded weights.

Both sides compute in float32 here (``tpu__COMPUTE_DTYPE="float32"`` on the
program's), so what separates them is the order of summation: the
tolerances below are a few float32 roundings, and a wrong window cut,
relative table, head split, deconv tap, LayerNorm axis or flatten order
would miss by the spread itself.  The bfloat16 program is held to the
reference by the comparison a run uses (``test_vitdet_run.py``)."""

import json

import jax
import numpy as np
import pytest

from benchmark import harness, loadgen
from benchmark.reference import frcnn_c4, mrcnn_vitdet
from benchmark.weights import as_tree, check_against

from . import tiny_vitdet

SEED = 2 ** 31 + 7
BOXES = np.asarray([[5.2, 4.1, 30.7, 28.3], [0.0, 0.0, 100.0, 70.0],
                    [40.5, 10.0, 41.2, 60.0], [60.3, 5.5, 95.9, 66.2]],
                   np.float32)
LABELS = np.asarray([1, 17, 80, 44], np.int32)


@pytest.fixture(scope="module")
def both():
    from mx_rcnn_tpu.data.loader import prepare_image
    from mx_rcnn_tpu.eval.tester import Predictor
    from mx_rcnn_tpu.models.detector import build_model, init_params

    spec = tiny_vitdet.tiny_spec()
    config, net = spec["config"], spec["config"]["net"]
    flat = harness.modules_of(config)["weights"].make(net, SEED)
    # a portrait body: the padding lies on the right of the square bucket
    doc = json.loads(loadgen.make_bodies(spec["traffic"]["bodies"], SEED)[3])
    assert doc["shape"][0] > doc["shape"][1]
    stages = {}
    dense = mrcnn_vitdet.detect(flat, doc, net, stages=stages)

    cfg = tiny_vitdet.program_cfg(tpu__COMPUTE_DTYPE="float32")
    model = build_model(cfg)
    check_against(flat, jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0))))
    pred = Predictor(model, as_tree(flat), cfg)
    image, im_info = prepare_image(frcnn_c4.decode_body(doc), cfg,
                                   cfg.tpu.SCALES[0])
    feats = jax.jit(lambda p, x: model.apply(
        {"params": p}, x, method=model._pyramid))(as_tree(flat), image[None])
    out = [np.asarray(o)[0] for o in pred.predict(image[None], im_info[None])]
    probs = np.asarray(jax.device_get(pred.predict_masks_cached(
        (BOXES * im_info[2])[None], LABELS[None], pred.feats_token)))[0]
    return net, dense, stages, image, im_info, feats, out, probs


def test_the_square_bucket_and_the_pyramid(both):
    """The same 96 x 96 input on both sides (the reference resizes and pads
    for itself; the program's loader hands its rows flattened, (H, W x 3),
    the same memory), and P2..P6 of both to 2e-5 of each level's spread: patch
    embedding, four blocks of both kinds, the simple feature pyramid."""
    net, dense, st, image, im_info, feats, _, _ = both
    assert image.shape == (96, 96 * 3) and st["image"].shape == (96, 96, 3)
    np.testing.assert_allclose(image.reshape(96, 96, 3), st["image"],
                               atol=1e-5)
    assert tuple(im_info[:2]) == st["im_info"][:2] and st["im_info"][1] < 96
    assert [tuple(f.shape[1:3]) for f in feats] == [
        (24, 24), (12, 12), (6, 6), (3, 3), (2, 2)]
    from benchmark.vitdet.weights import LEVEL_GAINS
    gains = [abs(LEVEL_GAINS[lvl]) for lvl in (2, 3, 4, 5, 5)]
    for mine, theirs, g in zip(feats, st["feats"], gains):
        np.testing.assert_allclose(mine, theirs, atol=2e-5 * max(g, 1))
        # every level ends in a LayerNorm at its level's gain
        assert 0.9 * g < float(np.std(theirs)) < 1.1 * g
    assert [tuple(f.shape) for f in dense["feats"]] == [
        (24, 24, 256), (12, 12, 256), (6, 6, 256), (3, 3, 256)]


def test_proposals_scores_and_deltas(both):
    """The 2-conv RPN over five levels, one joint NMS, RoIAlign on the
    assigned level, the 4-conv + FC head: the same 60 RoIs in the same
    order, class probabilities to 1e-5 and deltas to 1e-5."""
    net, _, st, _, _, _, (rois, valid, prob, deltas, scores), _ = both
    n = len(st["rois"])
    assert n == int(valid.sum()) == 60
    np.testing.assert_allclose(rois[:n], st["rois"], atol=1e-3)
    np.testing.assert_allclose(scores[:n], st["roi_scores"], atol=1e-5)
    np.testing.assert_allclose(prob[:n], st["prob"], atol=1e-5)
    np.testing.assert_allclose(deltas[:n], st["deltas"], atol=1e-5)
    # decisive outputs: objectness spread by a unit or more on the levels
    # that have room for it, class scores away from 1 / K
    spread = [float(np.std(lg[:, 1] - lg[:, 0])) for lg, _, _ in
              st["per_level"]]
    assert min(spread[:3]) > 1.0
    assert st["prob"][:, 1:].max(1).mean() > 5.0 / net["num_classes"]


def test_mask_probabilities_at_given_boxes(both):
    """28 x 28 maps of four boxes through the LayerNorm mask head:
    probabilities to 2e-4; the maps are decisive and differ box to box."""
    net, dense, _, _, _, _, _, probs = both
    want = mrcnn_vitdet.mask_probs(dense, BOXES, LABELS, net)
    assert probs.shape == want.shape == (4, 28, 28)
    np.testing.assert_allclose(probs, want, atol=2e-4)
    assert np.mean(np.abs(want - 0.5) > 0.1) > 0.5
    assert 0.15 < np.mean(want >= 0.5) < 0.85
    assert not np.allclose(want[0], want[3], atol=0.05)
    other = mrcnn_vitdet.mask_probs(dense, BOXES[:1], [2], net)
    assert not np.allclose(other[0], want[0], atol=0.05)


def test_the_reference_stands_alone():
    """It imports nothing of the program, no flax and no kernel; its float8
    mode (the control, ``test_vitdet_run.py``) rounds the attention's
    inputs through the same ``_prep`` every other matmul's go through."""
    import re
    src = open(mrcnn_vitdet.__file__).read()
    assert not re.search(r"^\s*(from|import)\s+mx_rcnn_tpu", src, re.M)
    assert "flax" not in src and "pallas" not in src
    assert src.count("_mm(") >= 5 and "HIGHEST" in src
    q = jax.numpy.asarray(np.linspace(-3, 3, 64, dtype=np.float32)
                          .reshape(1, 1, 8, 8))
    exact = mrcnn_vitdet._mm("nhqd,nhkd->nhqk", q, q, "f32")
    low = mrcnn_vitdet._mm("nhqd,nhkd->nhqk", q, q, "fp8")
    assert 0 < float(np.abs(exact - low).max()) < 0.1 * float(
        np.abs(exact).max())
