"""Cascade Mask R-CNN (``--network resnet101_fpn_cascade_mask``): the decode
step of a RoI cascade, stage 1 as the mask preset's predict program, stages
2.. as a program of their own chained behind it on device arrays, the host
post-process of one box a RoI, the engine and the evaluator serving it, the
paths that refuse it, one training step and the fold of each stage's
weights — and the other pyramid presets' trees and programs as they were.

One tiny real model for the file (ResNet-50 body on the cascade preset, a
64x96 bucket, 32 RoIs, 10 records an image, batch 2)."""

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu import native
from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.data import prepare_image
from mx_rcnn_tpu.eval import Predictor
from mx_rcnn_tpu.eval.tester import im_detect
from mx_rcnn_tpu.models import build_model, init_params
from mx_rcnn_tpu.ops.boxes import BBOX_XFORM_CLIP, refine_boxes
from mx_rcnn_tpu.ops.postprocess import (decode_image_boxes,
                                         detections_to_records, image_boxes,
                                         per_class_nms)
from mx_rcnn_tpu.serve import ServeEngine, ServeOptions, warmup

CAP = 10


def tiny(network="resnet101_fpn_cascade_mask", **over):
    over = dict(dict(TEST__RPN_PRE_NMS_TOP_N=250, TEST__RPN_POST_NMS_TOP_N=32,
                     TEST__MAX_PER_IMAGE=CAP), **over)
    cfg = generate_config(network, "coco", **over)
    net = dataclasses.replace(cfg.network, NETWORK="resnet50",
                              PIXEL_STDS=(127.0, 127.0, 127.0))
    tpu = dataclasses.replace(cfg.tpu, SCALES=((64, 96),))
    return cfg.replace(network=net, tpu=tpu)


def tree_shapes(cfg):
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: init_params(model, cfg,
                                                jax.random.PRNGKey(0)))
    return {"/".join(str(k.key) for k in path): tuple(s.shape)
            for path, s in jax.tree_util.tree_flatten_with_path(shapes)[0]}


def wait_booked(engine):
    """A future resolves before its turn is booked (SKILL.md)."""
    with engine._cond:
        while engine._inflight:
            engine._cond.wait(timeout=0.05)


@pytest.fixture(scope="module")
def served():
    cfg = tiny()
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0), 2, (64, 96))
    # stages that move boxes and score apart from stage 1, as trained ones
    # do (the init's box outputs are all but zero)
    rng = np.random.default_rng(5)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + rng.normal(0, 0.05, x.shape).astype(x.dtype)
        if "rcnn_out" in jax.tree_util.keystr(path) else x, params)
    pred = Predictor(model, params, cfg)
    engine = ServeEngine(pred, cfg, ServeOptions(
        batch_size=2, max_delay_ms=5.0, max_queue=16)).start()
    compiled = warmup(engine)
    yield cfg, model, params, pred, engine, compiled
    engine.stop()


def test_refine_boxes_is_the_decode_with_the_clamp_and_the_clip():
    boxes = np.asarray([[10, 20, 49, 59], [0, 0, 9, 9], [30, 5, 34, 94]],
                       np.float32)
    deltas = np.asarray([[0.1, -0.2, 0.3, -0.1], [0.0, 0.0, 9.0, 0.5],
                         [-2.0, 0.5, 0.0, 8.0]], np.float32)
    got = np.asarray(refine_boxes(boxes, deltas, 80.0, 60.0))
    w = boxes[:, 2] - boxes[:, 0] + 1
    h = boxes[:, 3] - boxes[:, 1] + 1
    cx, cy = boxes[:, 0] + 0.5 * (w - 1), boxes[:, 1] + 0.5 * (h - 1)
    dw = np.minimum(deltas[:, 2], np.log(1000 / 16))
    dh = np.minimum(deltas[:, 3], np.log(1000 / 16))
    pcx, pcy = deltas[:, 0] * w + cx, deltas[:, 1] * h + cy
    pw, ph = np.exp(dw) * w, np.exp(dh) * h
    want = np.stack([pcx - 0.5 * (pw - 1), pcy - 0.5 * (ph - 1),
                     pcx + 0.5 * (pw - 1), pcy + 0.5 * (ph - 1)], 1)
    want[:, 0::2] = np.clip(want[:, 0::2], 0, 59)
    want[:, 1::2] = np.clip(want[:, 1::2], 0, 79)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-4)
    assert BBOX_XFORM_CLIP == pytest.approx(np.log(62.5))
    assert got[1, 2] == 59 and got[2, 3] == 79      # clamped, then clipped


@pytest.mark.parametrize("network", ["resnet101_fpn_mask", "resnet101_fpn"])
def test_the_other_pyramid_presets_keep_their_trees_and_one_program(network):
    """Their parameter trees are the accepted benchmark's lists (the names
    and shapes the driver checks a run against), and their predictor
    registers no cascade program and hands back per-class deltas."""
    from benchmark.fpn import weights as fpn_weights
    from benchmark.mask import weights as mask_weights

    cfg = tiny(network)
    net = {"depth": "resnet50", "num_classes": 81, "num_anchors": 3,
           "fpn_channels": 256, "head_hidden": 1024, "mask_channels": 256,
           "mask_convs": 4}
    lists = mask_weights if cfg.network.HAS_MASK else fpn_weights
    want = {p: tuple(s) for p, s, *_ in lists.leaf_specs(net)}
    assert tree_shapes(cfg) == want
    assert cfg.network.ROI_CASCADE_STAGES == 1
    model = build_model(cfg)
    pred = Predictor(model, init_params(model, cfg, jax.random.PRNGKey(0), 1,
                                        (64, 96)), cfg)
    assert "roi_cascade" not in pred.registry._builders
    img, info = prepare_image(np.zeros((40, 60, 3), np.uint8), cfg,
                              cfg.tpu.SCALES[0])
    rois, valid, prob, deltas, _ = pred.predict(img[None], info[None])
    assert deltas.shape == (1, 32, 4 * 81) and prob.shape == (1, 32, 81)
    assert sorted({k.kind for k in pred.registry._seen}) == [
        "predict_wf" if cfg.network.HAS_MASK else "predict"]


def test_stage_one_is_the_mask_presets_predict_program(served):
    """With stage 1's weights taken from a mask network (its box output =
    the mask network's class-1 columns) the cascade's predict program gives
    the mask network's RoIs, probabilities and scores bit for bit, and
    those deltas."""
    cfg, model, params, _, _, _ = served
    mcfg = tiny("resnet101_fpn_mask")
    mmodel = build_model(mcfg)
    mparams = init_params(mmodel, mcfg, jax.random.PRNGKey(3), 1, (64, 96))
    shared = dict(params, **{k: v for k, v in mparams.items()})
    shared["rcnn_out"] = dict(mparams["rcnn_out"], bbox_pred={
        "kernel": mparams["rcnn_out"]["bbox_pred"]["kernel"][:, 4:8],
        "bias": mparams["rcnn_out"]["bbox_pred"]["bias"][4:8]})
    img, info = prepare_image(
        np.random.default_rng(2).integers(0, 256, (50, 70, 3), np.uint8),
        cfg, cfg.tpu.SCALES[0])
    x, i = jnp.asarray(img[None]), jnp.asarray(info[None])
    (r1, v1, p1, d1, s1), _ = mmodel.apply(
        {"params": mparams}, x, i, method=mmodel.predict_with_feats)
    (r2, v2, p2, d2, s2), _ = model.apply(
        {"params": shared}, x, i, method=model.predict_with_feats)
    for a, b in ((r1, r2), (v1, v2), (p1, p2), (s1, s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert d2.shape == (1, 32, 4)
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d1)[..., 4:8],
                               atol=1e-6)


def test_predict_chains_the_stages_program_on_device_arrays(served):
    """``Predictor.predict`` launches ``roi_cascade`` behind predict with
    predict's own device arrays: its outputs are the model's stages applied
    to them; the program's jitted function is named so that the device
    trace reads ``jit_roi_cascade`` (which ``^jit_f`` does not match), and
    its stages carry their scopes into the op metadata."""
    import re

    cfg, model, params, pred, _, _ = served
    img, info = prepare_image(
        np.random.default_rng(4).integers(0, 256, (64, 90, 3), np.uint8),
        cfg, cfg.tpu.SCALES[0])
    images, im_info = np.stack([img, img]), np.stack([info, info])
    rois, valid, scores, boxes, _ = pred.predict(images, im_info)
    assert all(isinstance(a, jax.Array) for a in (rois, scores, boxes))
    assert boxes.shape == (2, 32, 4) and scores.shape == (2, 32, 81)
    # the same two programs, jitted apart from the predictor
    (r, v, p1, d1, _), feats = jax.jit(lambda p, x, i: model.apply(
        {"params": p}, x, i, method=model.predict_with_feats))(
        params, images, im_info)
    b, s, v2 = jax.jit(lambda p, *a: model.apply(
        {"params": p}, *a, method=model.roi_cascade))(
        params, feats, r, v, p1, d1, im_info)
    np.testing.assert_allclose(np.asarray(boxes), np.asarray(b), atol=1e-3)
    np.testing.assert_allclose(np.asarray(scores), np.asarray(s), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(v2))
    # the stages moved the boxes and the scores away from stage 1's
    b2 = refine_boxes(r, d1, float(info[0]), float(info[1]))
    assert float(jnp.abs(b - b2).max()) > 0.5
    assert float(jnp.abs(s - p1).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(s).sum(-1), 1.0, atol=1e-5)
    fn = pred.registry._builders["roi_cascade"]()
    lowered = fn.lower(pred.params, feats, r, v, p1, d1,
                       jnp.asarray(im_info))
    assert "@jit_roi_cascade" in lowered.as_text()
    assert not re.search("^jit_f", "jit_roi_cascade")
    text = lowered.compile().as_text()
    for t in (2, 3):
        for part in ("pool", "head", "decode"):
            assert f"roi_cascade/s{t}/{part}" in text


@pytest.mark.parametrize("path", ["native", "loop"])
def test_one_box_a_roi_gives_the_records_of_the_box_tiled_to_every_class(
        path):
    """``per_class_nms`` over (R, 4) boxes equals it over the same boxes
    tiled K times, on the native call and on the Python loop alike."""
    rng = np.random.default_rng(0)
    r, k = 200, 81
    x1y1 = rng.uniform(0, 300, (r, 2))
    boxes = np.concatenate([x1y1, x1y1 + rng.uniform(5, 80, (r, 2))],
                           1).astype(np.float32)
    scores = rng.dirichlet(np.full(k, 0.3), r).astype(np.float32)
    valid = rng.random(r) > 0.1
    nms_fn = None if path == "native" else native.nms
    if path == "native" and not native.available("mxr_nms_classes_shared"):
        pytest.skip("the native library did not build")
    one = per_class_nms(scores, boxes, valid, k, 1e-3, 0.3, 100, nms_fn)
    tiled = per_class_nms(scores, np.tile(boxes, (1, k)), valid, k, 1e-3,
                          0.3, 100, nms_fn)
    assert detections_to_records(one) == detections_to_records(tiled)
    assert sum(len(d) for d in one[1:]) == 100
    # the host's decode of one box a RoI is the division by the scale
    info = np.asarray([64, 96, 1.6], np.float32)
    np.testing.assert_allclose(image_boxes(boxes[:, :4] * 0, boxes, info),
                               boxes / np.float32(1.6), rtol=1e-6)
    deltas = rng.normal(0, 0.1, (r, 4 * k)).astype(np.float32)
    np.testing.assert_array_equal(image_boxes(boxes, deltas, info),
                                  decode_image_boxes(boxes, deltas, info))


def test_the_engine_serves_records_with_masks_and_counts_its_programs(
        served):
    """Three programs an orientation (predict, the stages, the mask
    branch), nothing compiled after warm-up; each batch counts one
    ``roi_cascade_batches``; every record carries its mask; and the served
    records are what the evaluator's im_detect + per_class_nms give for the
    same image."""
    cfg, model, params, pred, engine, compiled = served
    assert compiled == 6 == engine.counters["warmup_programs"]
    kinds = sorted(k.kind for k in pred.registry._seen)
    assert kinds.count("roi_cascade") == 2 and kinds.count("predict_wf") == 2
    before = dict(engine.counters)
    img = np.random.default_rng(11).integers(0, 256, (52, 75, 3),
                                             dtype=np.uint8)
    recs = [f.result(120) for f in [engine.submit(img) for _ in range(2)]]
    wait_booked(engine)
    assert recs[0] == recs[1] and 0 < len(recs[0]) <= CAP
    assert all(r["segmentation"]["size"] == [52, 75] for r in recs[0])
    c = engine.counters
    assert c["recompiles"] == 6
    batches = c["batches"] - before["batches"]
    assert batches >= 1
    assert c["roi_cascade_batches"] - before["roi_cascade_batches"] == batches
    assert c["post_nms_native"] - before["post_nms_native"] == (
        2 if native.available("mxr_nms_classes_shared") else 0)
    prepared, im_info = prepare_image(img, cfg, cfg.tpu.SCALES[0])
    batch = {"images": np.stack([prepared, prepared]),
             "im_info": np.stack([im_info, im_info])}
    scores, boxes, valid = im_detect(pred, batch)[0]
    assert boxes.shape == (32, 4)
    offline = detections_to_records(per_class_nms(
        scores, boxes, valid, 81, cfg.TEST.THRESH, cfg.TEST.NMS, CAP))
    assert [(r["cls"], r["score"], r["bbox"]) for r in offline] == \
        [(r["cls"], r["score"], r["bbox"]) for r in recs[0]]


def test_a_box_network_counts_no_cascade_batch():
    from tests.test_serve import FakePredictor, tiny_cfg

    cfg = tiny_cfg()
    engine = ServeEngine(FakePredictor(cfg), cfg, ServeOptions(
        batch_size=2, max_delay_ms=1.0)).start()
    try:
        engine.submit(np.zeros((40, 60, 3), np.uint8)).result(60)
        wait_booked(engine)
        assert engine.counters["batches"] >= 1
        assert engine.counters["roi_cascade_batches"] == 0
    finally:
        engine.stop()


def test_the_fused_paths_refuse_the_cascade_by_name(served, monkeypatch):
    cfg, _, _, pred, _, _ = served
    with pytest.raises(ValueError, match="--serve-e2e cannot serve a RoI "
                                         "cascade"):
        ServeEngine(pred, cfg, ServeOptions(serve_e2e=True))
    img = np.zeros((2, 64, 96, 12), np.float32)
    info = np.tile(np.asarray([[64, 96, 1.0]], np.float32), (2, 1))
    with pytest.raises(ValueError, match="--device-postprocess cannot run "
                                         "a RoI cascade"):
        pred.predict_detections(img, info, CAP, 1e-3)
    import test as test_cli

    monkeypatch.setattr(sys, "argv", [
        "test.py", "--network", "resnet101_fpn_cascade_mask", "--dataset",
        "coco", "--device-postprocess"])
    with pytest.raises(SystemExit, match="RoI cascade"):
        test_cli.test_rcnn(test_cli.parse_args())


def test_the_evaluator_scores_boxes_and_masks_of_the_cascade(tmp_path,
                                                             served):
    """``test.py``'s path (``pred_eval`` over a ``TestLoader``, overlapped
    and serial) on mini-COCO files: one box a RoI through the host NMS, the
    mask pass over the cached pyramid, bbox and segm scored (random
    weights: mechanics, not accuracy), the two loops alike."""
    from mx_rcnn_tpu.data import TestLoader
    from mx_rcnn_tpu.data.coco_dataset import COCODataset
    from mx_rcnn_tpu.eval import pred_eval
    from tests.fixtures import make_mini_coco

    _, model, params, _, _, _ = served
    make_mini_coco(str(tmp_path / "coco"), image_set="minitrain", n=2,
                   with_masks=True)
    cfg = tiny(TEST__MAX_PER_IMAGE=5, tpu__MAX_GT=8)
    imdb = COCODataset("minitrain", str(tmp_path / "data"),
                       str(tmp_path / "coco"))
    got = []
    for inflight in (2, 0):
        loader = TestLoader(imdb.gt_roidb(), cfg, batch_size=1)
        stats = pred_eval(Predictor(model, params, cfg), loader, imdb,
                          thresh=1e-3, with_masks=True, inflight=inflight)
        assert "bbox" in stats and "AP" in stats["segm"], stats
        got.append(stats)
    assert got[0] == got[1]


def test_one_training_step_of_three_stages_and_the_fold_of_each_stage():
    """The train graph at the tiny size: three stages sampled at IoU 0.5 /
    0.6 / 0.7, class-agnostic smooth-L1, the stage losses summed, finite,
    and every stage's head learning; the boxes between stages carry no
    gradient into the stage before.  Then the fold at save: each stage's
    output layer scaled by its own 1 / w_t, and back."""
    from mx_rcnn_tpu.data.mask import GT_MASK_SIZE
    from mx_rcnn_tpu.train.checkpoint import (denormalize_for_save,
                                              normalize_for_train,
                                              roi_cascade_stds)

    cfg = tiny(TRAIN__RPN_PRE_NMS_TOP_N=200, TRAIN__RPN_POST_NMS_TOP_N=32,
               TRAIN__BATCH_ROIS=16, tpu__COMPUTE_DTYPE="float32",
               tpu__MAX_GT=4)
    assert cfg.network.ROI_CASCADE_FG_THRESH == (0.5, 0.6, 0.7)
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0), 1, (64, 96))
    rng = np.random.default_rng(0)
    gt = jnp.asarray([[[8, 6, 50, 40], [30, 20, 90, 60]] + [[0] * 4] * 2],
                     jnp.float32)
    args = (jnp.asarray(rng.normal(size=(1, 32, 48, 12)), jnp.float32),
            jnp.asarray([[64, 96, 1.0]], jnp.float32), gt,
            jnp.asarray([[7, 3, 0, 0]], jnp.int32),
            jnp.asarray([[True, True, False, False]]),
            jax.random.PRNGKey(1))
    masks = jnp.ones((1, 4, GT_MASK_SIZE, GT_MASK_SIZE), jnp.float32)

    def loss(p):
        return model.apply({"params": p}, *args, gt_masks=masks)

    (total, aux), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        params)
    assert np.isfinite(float(total)) and float(total) > 0
    stage = [float(aux[f"roi_cascade_s{t}_cls_loss"])
             + float(aux[f"roi_cascade_s{t}_bbox_loss"]) for t in (1, 2, 3)]
    assert all(np.isfinite(stage)) and min(stage) > 0
    assert float(aux["rcnn_cls_loss"]) + float(aux["rcnn_bbox_loss"]) == \
        pytest.approx(sum(stage), rel=1e-5)
    assert np.isfinite(float(aux["mask_loss"]))
    for name in ("rcnn_out", "rcnn_out2", "rcnn_out3", "head_body2",
                 "head_body3"):
        g = jax.tree_util.tree_leaves(grads[name])
        assert all(np.isfinite(np.asarray(x)).all() for x in g), name
        assert max(float(jnp.abs(x).max()) for x in g) > 0, name
    assert grads["rcnn_out2"]["bbox_pred"]["kernel"].shape == (1024, 4)
    # stage 3's loss reaches the heads before it through no box
    last = jax.jit(jax.grad(lambda p: (
        lambda a: a["roi_cascade_s3_cls_loss"] + a["roi_cascade_s3_bbox_loss"]
    )(loss(p)[1])))(params)
    for name in ("head_body", "rcnn_out", "head_body2", "rcnn_out2"):
        assert all(float(jnp.abs(x).max()) == 0
                   for x in jax.tree_util.tree_leaves(last[name])), name
    assert float(jnp.abs(last["rcnn_out3"]["bbox_pred"]["kernel"]).max()) > 0

    stds = roi_cascade_stds(cfg)
    assert stds["rcnn_out3"] == pytest.approx((1 / 30, 1 / 30, 1 / 15,
                                               1 / 15))
    saved = denormalize_for_save(params, cfg)
    for t, name in enumerate(("rcnn_out", "rcnn_out2", "rcnn_out3"), 1):
        w = np.asarray(cfg.network.ROI_CASCADE_WEIGHTS[t - 1], np.float32)
        np.testing.assert_allclose(
            np.asarray(saved[name]["bbox_pred"]["kernel"]),
            np.asarray(params[name]["bbox_pred"]["kernel"]) / w, rtol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(saved[name]["cls_score"]["kernel"]),
            np.asarray(params[name]["cls_score"]["kernel"]))
    back = normalize_for_train(saved, cfg)
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)
