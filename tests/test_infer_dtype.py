"""Low-precision inference variants: bf16 parity vs f32, per-dtype
zero-steady-state-recompile, int8 structural sanity.

The bf16 "variant" casts float params to bfloat16 host-side and casts
outputs back to f32 in-program; compute is already COMPUTE_DTYPE (bf16
by default), so the only delta vs the f32 path is weight storage.  Its
parity is decided the way the benchmark decides ``correct``
(``benchmark/compare.py``, imported, not copied): every bf16 detection
record is matched by IoU to the f32 path's dense candidate of its class,
a response is read by its median record, and the two gaps must stay
under limits that lie between this variant's readings over six seeds
and a coarser control's (``test_bf16_parity_and_per_dtype_steady_state``
has the numbers).  The int8 tests below keep their own coarser pins.
"""

import dataclasses
import os

import jax
import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.data import prepare_image
from mx_rcnn_tpu.eval import Predictor
from mx_rcnn_tpu.models import build_model, init_params
from mx_rcnn_tpu.ops.postprocess import (decode_image_boxes,
                                         detections_to_records,
                                         per_class_nms)
from mx_rcnn_tpu.serve import ServeEngine, ServeOptions, warmup
from mx_rcnn_tpu.train.checkpoint import denormalize_for_save

SCORE_MARGIN = 0.03   # dets this close to THRESH may flip in/out — skip
SCORE_ATOL = 0.04


def tiny_cfg():
    cfg = generate_config(
        "resnet50", "PascalVOC",
        TEST__RPN_PRE_NMS_TOP_N=300, TEST__RPN_POST_NMS_TOP_N=32,
    )
    net = dataclasses.replace(cfg.network, ANCHOR_SCALES=(2, 4))
    tpu = dataclasses.replace(cfg.tpu, SCALES=((96, 128),), MAX_GT=8)
    return cfg.replace(network=net, tpu=tpu)


def conditioned_cfg():
    """The benchmark's tiny size (``tests/benchmark_checks/tiny.py``):
    ResNet-50 C4, COCO's 81 classes, 9 anchors, a 96x128 bucket, 300 -> 30
    proposals, the pixel scale ``--synthetic`` sets."""
    cfg = generate_config(
        "resnet50", "coco", tpu__SCALES=((96, 128),),
        TEST__RPN_PRE_NMS_TOP_N=300, TEST__RPN_POST_NMS_TOP_N=30)
    return cfg.replace(network=dataclasses.replace(
        cfg.network, PIXEL_STDS=(127.0, 127.0, 127.0)))


def conditioned_params(cfg, seed):
    """The benchmark's weights: activations O(1) through the frozen-BN
    trunk, decisive logits, box deltas of a tenth.  A plain random
    init saturates every score and amplifies one rounding step into tens
    of pixels on one corner, so two dtypes' detections are not
    comparable on it (a 4 px corner tolerance on such weights was red
    from PR 21 to PR 30)."""
    from benchmark import weights

    net = {"depth": "resnet50", "num_classes": cfg.NUM_CLASSES,
           "num_anchors": cfg.network.NUM_ANCHORS}
    return jax.tree.map(np.asarray, weights.as_tree(weights.make(net, seed)))


def dense_and_records(pred, cfg, img):
    """One image through the offline path, self-padded to batch 2:
    ((scores, boxes) of the valid RoIs — every class, before the
    per-class NMS — and the record list after it)."""
    prepared, im_info = prepare_image(img, cfg, cfg.tpu.SCALES[0])
    rois, valid, scores, deltas, _ = [
        np.asarray(jax.device_get(x)) for x in pred.predict(
            np.stack([prepared, prepared]), np.stack([im_info, im_info]))]
    boxes = decode_image_boxes(rois[0], deltas[0], im_info)
    records = detections_to_records(per_class_nms(
        scores[0], boxes, valid[0], cfg.NUM_CLASSES,
        cfg.TEST.THRESH, cfg.TEST.NMS, cfg.TEST.MAX_PER_IMAGE))
    return (scores[0][valid[0]], boxes[valid[0]]), records


def records_for(pred, cfg, img):
    """The record list alone (the serve batch shape, so the engine-warmed
    programs are reused)."""
    return dense_and_records(pred, cfg, img)[1]


def float8_weights(params):
    """The coarser control: every float leaf rounded to float8_e4m3
    under a per-tensor scale — 3 mantissa bits where bfloat16 keeps 7."""
    import jax.numpy as jnp

    def rounded(x):
        scale = np.abs(x).max() / 448.0    # e4m3's largest finite value
        if scale == 0:
            return x
        q = jnp.asarray(x / scale).astype(jnp.float8_e4m3fn)
        return np.asarray(q.astype(jnp.float32) * scale, x.dtype)

    return jax.tree.map(rounded, params)


# Readings on this configuration over seeds 1-6, four images a seed, 400
# records each, the three structure counts 0 in every run (CPU, PR 30):
#   bfloat16 weights   box_gap 0.0023-0.0038   score_gap 0.0058-0.0084
#   float8 control     box_gap 0.0169-0.2020   score_gap 0.0433-0.0528
#   (int8 weights      box_gap 0.0061-0.0078   score_gap 0.0160-0.0192)
# Each limit lies 2.1-2.3 x over the variant's largest reading and as far
# under the control's least.
BF16_LIMITS = {"records": 100, "box_gap": 0.008, "score_gap": 0.019,
               "order_faults": 0, "low_scores": 0, "nms_faults": 0}


def test_bf16_parity_and_per_dtype_steady_state():
    from benchmark.compare import compare, judge

    cfg = conditioned_cfg()
    model = build_model(cfg)
    params = conditioned_params(cfg, seed=1)

    pred32 = Predictor(model, params, cfg)
    pred16 = Predictor(model, params, cfg, dtype="bfloat16")
    assert pred32.registry.dtype == "float32"
    assert pred16.registry.dtype == "bfloat16"

    # bf16 behind a real engine: warmup readies one program per
    # orientation, steady-state traffic must add zero — per dtype
    engine = ServeEngine(pred16, cfg, ServeOptions(
        batch_size=2, max_delay_ms=5.0, max_queue=16)).start()
    try:
        assert warmup(engine) == 2
        rng = np.random.RandomState(7)
        images = [rng.randint(0, 255, (h, w, 3), dtype=np.uint8)
                  for h, w in ((60, 100), (100, 60))]
        for img in images:
            dets = engine.submit(img, deadline_ms=0).result(timeout=300.0)
            assert isinstance(dets, list)
        assert (engine.counters["recompiles"]
                == engine.counters["warmup_programs"] == 2)
        assert engine.counters["recompiles_bfloat16"] == 2
        assert engine.metrics()["dtype"] == "bfloat16"
        assert engine.metrics()["compile"]["dtype"] == "bfloat16"

        # parity on the warmed shapes: the bf16 records against the f32
        # path's dense candidates, and the control in bf16's place
        images += [rng.randint(0, 255, (h, w, 3), dtype=np.uint8)
                   for h, w in ((72, 110), (80, 120))]
        net = {"test_nms": cfg.TEST.NMS, "test_thresh": cfg.TEST.THRESH}
        dense = [dense_and_records(pred32, cfg, img)[0] for img in images]

        def numbers(pred):
            sample = [{"detections": dense_and_records(pred, cfg, img)[1]}
                      for img in images]
            return compare(sample, dense, net)

        ok, compared = judge(numbers(pred16), BF16_LIMITS)
        assert ok, compared
        # the f32 programs again, float8 weights: no new compile
        control = Predictor(model, float8_weights(params), cfg)
        ok, compared = judge(numbers(control), BF16_LIMITS)
        assert not ok, compared
    finally:
        engine.stop()

    # the two dtypes were separate programs end to end
    assert pred16.registry.snapshot()["programs"]
    assert all(p["dtype"] == "bfloat16"
               for p in pred16.registry.snapshot()["programs"])
    assert all(p["dtype"] == "float32"
               for p in pred32.registry.snapshot()["programs"])


def _iou(a, b):
    ix = max(0.0, min(a[2], b[2]) - max(a[0], b[0]))
    iy = max(0.0, min(a[3], b[3]) - max(a[1], b[1]))
    inter = ix * iy
    area = ((a[2] - a[0]) * (a[3] - a[1])
            + (b[2] - b[0]) * (b[3] - b[1]) - inter)
    return inter / area if area > 0 else 0.0


IOU_FLOOR = 0.3


def assert_matched_iou(src, dst, thresh, tag):
    """Every confident det in ``src`` has a same-class twin in ``dst``
    at the standard score delta whose box overlaps (IoU pin)."""
    for r in src:
        if r["score"] < thresh + SCORE_MARGIN:
            continue
        twins = [s for s in dst
                 if s["cls"] == r["cls"]
                 and abs(s["score"] - r["score"]) < SCORE_ATOL
                 and _iou(s["bbox"], r["bbox"]) >= IOU_FLOOR]
        assert twins, (tag, r, dst)


def test_int8_activation_calibration_parity_and_persistence(tmp_path):
    """The real quantized path (``--infer-dtype int8-activation``):
    calibration over a held-out shard yields a positive per-tensor scale
    for the network input, the manifest round-trips through the registry
    (persisted next to the AOT markers, keyed by config digest), a
    Predictor built without explicit scales auto-loads them, detections
    stay within the pinned int8 deltas of f32 (and of the weight-only
    int8 variant), and repeat dispatch on the warmed shape adds zero
    programs per dtype."""
    cfg = tiny_cfg()
    model = build_model(cfg)
    params = denormalize_for_save(
        init_params(model, cfg, jax.random.PRNGKey(0), 2, (96, 128)), cfg)

    from mx_rcnn_tpu.compile import ProgramRegistry
    from mx_rcnn_tpu.eval.tester import calibrate_activation_scales

    rng = np.random.RandomState(5)
    shard = [rng.randint(0, 255, (60, 100, 3), dtype=np.uint8)
             for _ in range(2)]
    with pytest.raises(ValueError, match="empty"):
        calibrate_activation_scales(model, params, cfg, [])
    scales = calibrate_activation_scales(model, params, cfg, shard,
                                         max_images=1)
    assert scales["images"]["scale"] > 0.0
    assert scales["images"]["absmax"] > 0.0

    # persistence round-trip, digest-keyed next to the AOT manifest
    reg = ProgramRegistry(cfg, dtype="int8-activation",
                          cache_base=str(tmp_path))
    path = reg.save_act_scales(scales)
    assert path and os.path.exists(path)
    assert ProgramRegistry(cfg, dtype="int8-activation",
                           cache_base=str(tmp_path)).load_act_scales() \
        == scales

    # auto-load: no explicit act_scales, same cache + config digest
    pred8a = Predictor(model, params, cfg, dtype="int8-activation",
                       cache_base=str(tmp_path))
    assert pred8a.act_scales == scales
    assert pred8a.registry.dtype == "int8-activation"

    pred8 = Predictor(model, params, cfg, dtype="int8")
    img = shard[0]
    r8 = records_for(pred8, cfg, img)
    r8a = records_for(pred8a, cfg, img)
    # the fake-quant must actually engage: with a calibrated scale the
    # activation path cannot be byte-identical to weight-only int8
    assert any(abs(a["score"] - b["score"]) > 0
               for a, b in zip(r8, r8a)) or \
        any(not np.allclose(a["bbox"], b["bbox"])
            for a, b in zip(r8, r8a))
    # the pin isolates exactly what this variant ADDS: activation
    # fake-quant on top of the shared weight quantization.  Scores hold
    # the standard (bf16-grade) delta; boxes are pinned by IoU, not
    # corner atol — on RANDOM-init weights the in-graph exp(dh) box
    # regression amplifies a one-step input perturbation into tens of
    # px on a single corner while the object region (and every score)
    # stays put.  (Weight quant vs f32 flips proposal top-k outright,
    # so that pair stays the structural finiteness test below.)
    assert_matched_iou(r8, r8a, cfg.TEST.THRESH, "int8->int8a")
    assert_matched_iou(r8a, r8, cfg.TEST.THRESH, "int8a->int8")

    # zero steady-state recompiles per dtype: the warmed shape re-serves
    # from the same program
    n_prog = len(pred8a.registry.snapshot()["programs"])
    records_for(pred8a, cfg, img)
    snap = pred8a.registry.snapshot()
    assert len(snap["programs"]) == n_prog
    assert all(p["dtype"] == "int8-activation" for p in snap["programs"])


def test_int8_variant_runs_and_is_finite():
    cfg = tiny_cfg()
    model = build_model(cfg)
    params = denormalize_for_save(
        init_params(model, cfg, jax.random.PRNGKey(0), 2, (96, 128)), cfg)
    pred = Predictor(model, params, cfg, dtype="int8")
    assert pred.registry.dtype == "int8"

    img = np.random.RandomState(3).randint(0, 255, (60, 100, 3),
                                           dtype=np.uint8)
    prepared, im_info = prepare_image(img, cfg, cfg.tpu.SCALES[0])
    rois, valid, scores, deltas, _ = [
        np.asarray(jax.device_get(x)) for x in pred.predict(
            np.stack([prepared, prepared]), np.stack([im_info, im_info]))]
    # weight quantization must not produce NaN/Inf anywhere downstream
    for name, arr in (("rois", rois), ("scores", scores),
                      ("deltas", deltas)):
        assert np.isfinite(arr).all(), name
    assert scores.dtype == np.float32  # outputs cast back to f32
    assert rois.shape[-1] == 4 and valid.dtype == bool
