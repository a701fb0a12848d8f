"""``FPNFasterRCNN.predict`` (through ``Predictor``) against the plain
reference ``benchmark/reference/frcnn_fpn.py`` at the tiny size, on seeded
random weights, stage by stage: pyramid levels, per-level RPN outputs, the
proposal set, the level map, pooled features, class probabilities and
deltas.

Both sides compute in float32 here (``tpu__COMPUTE_DTYPE="float32"`` on the
program's), so what separates them is the order of summation and nothing
else: every tolerance below is a few float32 roundings of the quantity's
own magnitude, and a wrong tap, stride, level or sample would miss it by
orders.  The bfloat16 program is held to the reference by the comparison a
run uses, in ``test_fpn_run.py``."""

import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, loadgen
from benchmark.reference import frcnn_c4, frcnn_fpn
from benchmark.weights import as_tree, check_against

from . import tiny_fpn


@pytest.fixture(scope="module")
def both():
    """The reference's stages and the program's, same weights, one
    landscape body of the tiny traffic (resized 1.6x into 128 x 192)."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.data.loader import prepare_image
    from mx_rcnn_tpu.eval.tester import Predictor
    from mx_rcnn_tpu.models.detector import build_model, init_params
    from mx_rcnn_tpu.ops.proposal import propose_fpn
    from mx_rcnn_tpu.models import losses as L

    spec = tiny_fpn.tiny_spec()
    config, net = spec["config"], spec["config"]["net"]
    flat = harness.modules_of(config)["weights"].make(net, 2 ** 31 + 7)
    pool = loadgen.make_bodies(spec["traffic"]["bodies"], 2 ** 31 + 7)
    doc = json.loads(pool[0])
    ref = {}
    frcnn_fpn.detect(flat, doc, net, stages=ref)

    cfg = generate_config(
        "resnet50_fpn", "coco", tpu__SCALES=((128, 192),),
        TEST__RPN_PRE_NMS_TOP_N=500, TEST__RPN_POST_NMS_TOP_N=60,
        tpu__COMPUTE_DTYPE="float32",
        network__PIXEL_STDS=tuple(net["pixel_stds"]))
    model = build_model(cfg)
    check_against(flat, jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0))))
    params = as_tree(flat)
    image, im_info = prepare_image(frcnn_c4.decode_body(doc), cfg,
                                   cfg.tpu.SCALES[0])
    images, infos = image[None], im_info[None]

    def stages(m, images, im_info):
        te = cfg.TEST
        feats = m._pyramid(images)
        levels = m._rpn_over_levels(feats)
        rois, scores, valid = jax.vmap(lambda ls, ld, info: propose_fpn(
            list(ls), list(ld), [a for _, _, a in levels], info[0], info[1],
            info[2], pre_nms_top_n=te.RPN_PRE_NMS_TOP_N,
            post_nms_top_n=te.RPN_POST_NMS_TOP_N,
            nms_thresh=te.RPN_NMS_THRESH, min_size=te.RPN_MIN_SIZE,
            use_pallas=te.CXX_PROPOSAL))(
                tuple(L.fg_prob(c) for c, _, _ in levels),
                tuple(b for _, b, _ in levels), im_info)
        return {"feats": feats, "levels": levels, "rois": rois,
                "roi_scores": scores, "roi_valid": valid,
                "lvl": m._assign_level(rois),
                "pooled": m._pool_levels(feats, rois, pooled=7)}

    prog = nn.apply(stages, model)({"params": params}, jnp.asarray(images),
                                   jnp.asarray(infos))
    out = Predictor(model, params, cfg).predict(images, infos)
    prog["predict"] = [np.asarray(x) for x in jax.device_get(out)]
    prog["model"], prog["params"], prog["cfg"] = model, params, cfg
    return ref, prog, (images, infos)


def test_the_prepared_image_is_the_references_regrouped(both):
    """Host prep: the program ships (H/2, W/2, 12), the reference's
    (H, W, 3) regrouped 2x2 space-to-depth in (di, dj, c) order; cv2's
    resize and the reference's own agree to float32 rounding."""
    ref, _, (images, infos) = both
    x = ref["image"]
    h, w, _ = x.shape
    s2d = x.reshape(h // 2, 2, w // 2, 2, 3).transpose(0, 2, 1, 3, 4).reshape(
        h // 2, w // 2, 12)
    np.testing.assert_allclose(images[0], s2d, atol=2e-6)
    np.testing.assert_allclose(infos[0], ref["im_info"], rtol=1e-6)


def test_pyramid_levels(both):
    """P2..P6, magnitudes of about 1-3 after 50 layers: 2e-4 absolute is a
    hundred float32 roundings; a nearest-neighbour shift or a wrong lateral
    would miss by the spread itself."""
    ref, prog, _ = both
    shapes = [(1, 32, 48, 256), (1, 16, 24, 256), (1, 8, 12, 256),
              (1, 4, 6, 256), (1, 2, 3, 256)]
    for want, got, shape in zip(ref["feats"], prog["feats"], shapes):
        assert got.shape == shape == want.shape
        np.testing.assert_allclose(got, want, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(prog["feats"][4]),
                                  np.asarray(prog["feats"][3])[:, ::2, ::2])


def test_rpn_outputs_and_anchors_on_every_level(both):
    """One head over five levels: logits (spread 1-3) to 3e-4, deltas
    (spread 0.1) to 5e-5, the anchors exactly (integers of the same
    enumeration)."""
    ref, prog, _ = both
    sizes = []
    for (lg, dl, an), (cls, box, anchors) in zip(ref["per_level"],
                                                 prog["levels"]):
        np.testing.assert_allclose(cls[0], lg, atol=3e-4)
        np.testing.assert_allclose(box[0], dl, atol=5e-5)
        np.testing.assert_array_equal(np.asarray(anchors), an)
        sizes.append(len(an))
    assert sizes == [4608, 1152, 288, 72, 18]
    # one scale a level: the square anchor of level l is 8 * stride wide
    for (_, _, an), stride in zip(ref["per_level"], (4, 8, 16, 32, 64)):
        assert an[1, 2] - an[1, 0] + 1 == 8 * stride


def test_the_proposal_set_with_p6_under_k(both):
    """Per-level top-100 (P6 gives all 18), one joint NMS, the 60 best:
    the same boxes in the same order.  Boxes to 2e-3 px (a delta's 5e-5
    times a 512-px anchor, through exp), scores to 1e-5."""
    ref, prog, _ = both
    valid = np.asarray(prog["roi_valid"][0])
    n = len(ref["rois"])
    assert valid[:n].all() and not valid[n:].any() and n == 60
    np.testing.assert_allclose(np.asarray(prog["rois"][0])[:n], ref["rois"],
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(prog["roi_scores"][0])[:n],
                               ref["roi_scores"], atol=1e-5)


def test_level_map_of_the_proposals_and_of_every_level(both):
    """Eq. 1 on the proposals the run made, and on boxes made to land on
    every level and on both clamps (16 px -> P2, 2000 px -> P5); the edges
    at 112 / 224 / 448 px fall to the upper level."""
    ref, prog, _ = both
    np.testing.assert_array_equal(np.asarray(prog["lvl"][0]) + 2,
                                  ref["levels"])
    side = np.array([16, 111, 112, 223, 224, 447, 448, 2000], np.float32)
    rois = np.stack([0 * side, 0 * side, side - 1, side - 1], 1)
    want = [2, 2, 3, 3, 4, 4, 5, 5]
    assert list(frcnn_fpn.assign_level(rois)) == want
    got = nn.apply(lambda m, r: m._assign_level(r), prog["model"])(
        {"params": prog["params"]}, jnp.asarray(rois))
    assert list(np.asarray(got) + 2) == want
    from mx_rcnn_tpu.serve.engine import _roi_level_counts
    counts = _roi_level_counts(rois[None], np.ones((1, 8), bool))
    assert counts == {"rois_valid": 8, "rois_level_p2": 2, "rois_level_p3": 2,
                      "rois_level_p4": 2, "rois_level_p5": 2}


def test_pooled_features_of_the_proposals(both):
    """7x7 crops, 2x2 samples a bin, each RoI from its own level: features
    of magnitude 1-3 interpolated with weights that sum to 1 — 3e-4."""
    ref, prog, _ = both
    n = len(ref["rois"])
    np.testing.assert_allclose(np.asarray(prog["pooled"][0])[:n],
                               ref["crops"], atol=3e-4)


def test_pooling_on_every_level_and_past_the_maps_edge(both):
    """The run's own proposals reach P2 and P3 only (a 128 x 192 image
    holds no 224-px box), so boxes are made for P4 and P5 too, two of them
    reaching past the image: samples a cell or more outside contribute 0,
    the rest clamp — the reference's gather against the program's dense
    contraction."""
    ref, prog, _ = both
    rois = np.array([[10.3, 7.9, 40.2, 30.1], [0, 0, 150.5, 120.25],
                     [30, 20, 330, 290], [-40, -60, 500, 420],
                     [100.5, 60.5, 191, 127], [5, 5, 6, 6]], np.float32)
    levels = frcnn_fpn.assign_level(rois)
    assert sorted(set(levels)) == [2, 3, 4, 5]
    want = frcnn_fpn.pool_on_levels(ref["feats"], rois, levels,
                                    (4, 8, 16, 32), block=4)
    got = nn.apply(lambda m, f, r: m._pool_levels(f, r, pooled=7),
                   prog["model"])({"params": prog["params"]},
                                  prog["feats"], jnp.asarray(rois[None]))
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=3e-4)
    # the 540 x 480 box on P5's 4 x 6 map: its last rows' samples lie past
    # the map, its first rows' inside
    assert np.abs(want[3][1]).max() > 0 and (want[3][-1] == 0).all()


def test_class_probabilities_and_deltas_through_predictor(both):
    """What ``Predictor.predict`` returns — the serving path's readback —
    against the reference's head on its own crops: probabilities to 2e-5
    (logits of a few units to 1e-4), deltas of about 0.1 to 2e-5."""
    ref, prog, _ = both
    rois, valid, prob, deltas, _ = prog["predict"]
    n = len(ref["rois"])
    assert prob.shape == (1, 60, 81) and deltas.shape == (1, 60, 324)
    assert valid[0, :n].all()
    np.testing.assert_allclose(rois[0, :n], ref["rois"], atol=2e-3)
    np.testing.assert_allclose(prob[0, :n], ref["prob"], atol=2e-5)
    np.testing.assert_allclose(deltas[0, :n], ref["deltas"], atol=2e-5)
    # decisive outputs (benchmark/fpn/weights.py): not a field of ties
    assert ref["prob"].max(1).mean() > 0.2
    assert 0.03 < ref["deltas"].std() < 0.3


@pytest.mark.parametrize("hw", [(64, 96), (96, 64)],
                         ids=["landscape", "portrait"])
def test_the_s2d_stem_is_the_plain_7x7_stride_2_conv(hw):
    """``StemConvS2D`` on the host-regrouped (H/2, W/2, 12) image against
    the reference's plain conv on (H, W, 3), even sizes, both orientations,
    with the folded BN: float32 on both sides, sums of 147 products of
    magnitude 1 — 1e-4."""
    from mx_rcnn_tpu.data.image import space_to_depth2
    from mx_rcnn_tpu.models.backbones import StemConvS2D

    rng = np.random.default_rng(hw[0])
    x = rng.normal(size=(*hw, 3)).astype(np.float32)
    k = rng.normal(size=(7, 7, 3, 64)).astype(np.float32) / 12
    scale = rng.uniform(0.5, 1.5, 64).astype(np.float32)
    shift = rng.normal(size=64).astype(np.float32)
    stem = StemConvS2D(dtype=jnp.float32)
    got = stem.apply({"params": {"kernel": k}},
                     jnp.asarray(space_to_depth2(x)[None]), scale, shift)
    want = frcnn_c4.conv(jnp.asarray(x[None]), jnp.asarray(k), 2,
                         "f32") * scale + shift
    assert got.shape == (1, hw[0] // 2, hw[1] // 2, 64)
    np.testing.assert_allclose(got, want, atol=1e-4)
    # and regrouped on the device, from the 3-channel image
    again = stem.apply({"params": {"kernel": k}}, jnp.asarray(x[None]),
                       scale, shift)
    np.testing.assert_allclose(again, want, atol=1e-4)


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    for mod in (frcnn_fpn, frcnn_c4):
        for node in ast.walk(ast.parse(inspect.getsource(mod))):
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import) else
                     [node.module or ""] if isinstance(node, ast.ImportFrom)
                     else [])
            assert not any(n.startswith("mx_rcnn_tpu") or n == "serve"
                           for n in names), names


def test_joint_nms_instead_of_detectrons_per_level_nms_is_followed(both):
    """``assumed``: ONE greedy NMS over the concatenated levels.  Two boxes
    of different levels that overlap by more than 0.7 cannot both be
    proposals, which per-level NMS would allow."""
    ref, _, _ = both
    rois = ref["rois"]
    worst = max(float(frcnn_c4.iou_one_many(rois[i], rois[i + 1:]).max())
                for i in range(len(rois) - 1))
    assert worst <= 0.7 + 1e-6
