"""``r101-fpn-mask``'s own modules and data, without a server: the weights'
tree against the program's, the operation count by hand, the comparison's
mask half on hand-made masks (what it flags and what it lets pass), and
what the configuration's and the cell's files state."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import harness
from benchmark.mask import compare as mcompare
from benchmark.mask import control, flops, weights
from benchmark.reference import mrcnn_fpn

from . import tiny_mask

SPEC = harness.load_cell("mask-serve-closed")
NET = SPEC["config"]["net"]


# ----------------------------------------------------------------- weights

def test_mask_weights_are_the_programs_tree_at_the_published_widths():
    """Names and shapes only (nothing is drawn): the reference's lists of
    layers against the program's ResNet-101-FPN-mask parameter tree."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models.detector import build_model, init_params

    cfg = generate_config("resnet101_fpn_mask", "coco",
                          tpu__SCALES=((128, 192),))
    model = build_model(cfg)
    theirs = {"/".join(str(k.key) for k in path): tuple(s.shape)
              for path, s in jax.tree_util.tree_flatten_with_path(
                  jax.eval_shape(lambda: init_params(
                      model, cfg, jax.random.PRNGKey(0))))[0]}
    ours = dict(weights.leaf_specs(NET))
    assert ours == theirs
    mask = {k: v for k, v in ours.items() if k.startswith("mask_head/")}
    assert len(mask) == 12
    assert mask["mask_head/mask_conv1/kernel"] == (3, 3, 256, 256)
    assert mask["mask_head/mask_deconv/kernel"] == (2, 2, 256, 256)
    assert mask["mask_head/mask_out/kernel"] == (1, 1, 256, 81)
    assert mask["mask_head/mask_out/bias"] == (81,)


def test_every_seed_is_the_same_mask_head_in_another_order():
    net = tiny_mask.tiny_spec()["config"]["net"]
    a, b = weights.make(net, 1), weights.make(net, 2 ** 31 + 9)
    assert set(a) == {p for p, _ in weights.leaf_specs(net)}
    assert all(v.dtype == np.float32 for v in a.values())
    for k in ("mask_head/mask_conv2/kernel", "mask_head/mask_conv4/bias",
              "mask_head/mask_deconv/kernel", "mask_head/mask_out/kernel"):
        assert not np.array_equal(a[k], b[k]), k
        np.testing.assert_array_equal(np.sort(np.ravel(a[k])),
                                      np.sort(np.ravel(b[k])))
    crops = jax.numpy.asarray(0.8 * np.random.default_rng(0).normal(
        size=(3, 14, 14, 256)).astype(np.float32))
    pa = np.asarray(mrcnn_fpn.mask_head(a, crops))
    pb = np.asarray(mrcnn_fpn.mask_head(b, crops))
    assert pa.shape == (3, 28, 28, 81)
    np.testing.assert_allclose(pa, pb, atol=2e-5)
    # decisive maps: logits spread by a few units on crops of a pooled
    # pyramid's spread
    pa = np.clip(pa.astype(np.float64), 1e-9, 1 - 1e-9)
    assert 1.5 < np.log(pa / (1 - pa)).std() < 8
    again = weights.make(net, 2 ** 31 + 9)
    assert all(np.array_equal(b[k], again[k]) for k in b)


def test_a_program_without_the_mask_stage_ends_the_run_at_once(monkeypatch):
    """The parent of PR 32 under this PR's benchmark files: it builds the
    network and would serve boxes alone.  ``make`` refuses before anything
    is drawn; this tree passes."""
    from mx_rcnn_tpu.serve.engine import ServeEngine

    weights.require_mask_serving()
    monkeypatch.delattr(ServeEngine, "_mask_stage")
    with pytest.raises(SystemExit, match="no mask stage") as e:
        weights.make(tiny_mask.tiny_spec()["config"]["net"], 1)
    assert e.value.code not in (0, None)


def test_the_deconv_is_the_programs():
    """flax's ConvTranspose on the same kernel: the reference's 2x2
    stride-2 block writes the same cells."""
    import flax.linen as nn

    rng = np.random.default_rng(2)
    k = rng.normal(size=(2, 2, 8, 8)).astype(np.float32)
    x = rng.normal(size=(2, 14, 14, 8)).astype(np.float32)
    p = {f"mask_head/mask_conv{i}/{leaf}": v for i in range(1, 5)
         for leaf, v in (("kernel", np.zeros((3, 3, 8, 8), np.float32)),
                         ("bias", np.zeros(8, np.float32)))}
    # four convs that pass x on: a centre tap of one
    for i in range(1, 5):
        p[f"mask_head/mask_conv{i}/kernel"][1, 1] = np.eye(8)
    p.update({"mask_head/mask_deconv/kernel": k,
              "mask_head/mask_deconv/bias": np.zeros(8, np.float32),
              "mask_head/mask_out/kernel": np.eye(8, dtype=np.float32)[
                  None, None],
              "mask_head/mask_out/bias": np.zeros(8, np.float32)})
    got = np.asarray(mrcnn_fpn.mask_head(p, np.maximum(x, 0)))
    want = 1 / (1 + np.exp(-np.maximum(np.asarray(nn.ConvTranspose(
        8, (2, 2), strides=(2, 2)).apply({"params": {
            "kernel": k, "bias": np.zeros(8, np.float32)}},
            np.maximum(x, 0))), 0)))
    np.testing.assert_allclose(got, want, atol=1e-5)


# ------------------------------------------------------------------- flops

def test_mask_flops_by_hand():
    """A RoI: four 3x3 convs of 256 on 14x14, the deconv (196 cells x four
    taps), the 1x1 to 81 maps on 28x28 = 530 MMAC; the predict program's
    count is ``r101-fpn``'s."""
    conv, deconv, out = 14 * 14 * 9 * 256 * 256, 196 * 4 * 256 * 256, \
        28 * 28 * 256 * 81
    assert (conv, deconv, out) == (115605504, 51380224, 16257024)
    assert flops.mask_macs_per_roi(NET) == 4 * conv + deconv + out == 530059264
    assert flops.mask_flops_per_image(NET, 100) == 2 * 100 * 530059264
    assert flops.mask_flops_per_image(NET, 0) == 0
    from benchmark.fpn import flops as fpn_flops

    assert flops.predict_flops_per_image(NET) == \
        fpn_flops.predict_flops_per_image(NET)
    assert round(flops.predict_flops_per_image(NET)["total"] / 1e9) == 581
    kinds = [layer[1] for layer in mrcnn_fpn.mask_layers()]
    assert kinds == ["conv"] * 4 + ["deconv", "out"]    # no pooling entry


# -------------------------------------------------------- the comparison

H, W = 40, 60
BOX = [10.2, 5.5, 29.7, 24.1]            # window x 10..30, y 5..25


def _reference_map():
    """A pasted probability map over BOX's window: a firm disc (0.95) on a
    firm ground (0.05) with a ring of 0.51 / 0.49 between them."""
    yy, xx = np.mgrid[0:21, 0:21]
    r = np.hypot(yy - 10, xx - 10)
    prob = np.where(r < 6, 0.95, np.where(r < 7, 0.51,
                                          np.where(r < 8, 0.49, 0.05)))
    return (10, 5), prob


def _record(bits_in_window, size=(H, W), box=BOX, frame=None):
    frame = np.zeros((H, W), bool) if frame is None else frame
    frame[5:26, 10:31] |= bits_in_window
    seg = control.encode_mask(frame)
    seg["size"] = list(size)
    return {"cls": 3, "score": 0.9, "bbox": list(box), "segmentation": seg}


def _numbers(recs, monkeypatch, net_extra=None):
    origin, prob = _reference_map()
    monkeypatch.setattr(mcompare.mrcnn_fpn, "masks",
                        lambda d, boxes, labels, net: [(origin, prob)
                                                       for _ in boxes])
    monkeypatch.setattr(mcompare.boxes_compare, "compare",
                        lambda sample, dense, net: {"records": 1.0})
    net = dict(NET, **(net_extra or {}))
    sample = [{"doc": {"shape": [H, W, 3]}, "detections": recs}]
    dense = [{"prob": None, "boxes": None, "hw": (H, W)}]
    return mcompare.compare(sample, dense, net)


def test_a_faithful_mask_and_a_mask_that_differs_on_the_contour_pass(
        monkeypatch):
    _, prob = _reference_map()
    exact = _numbers([_record(prob >= 0.5)], monkeypatch)
    assert (exact["mask_missing"], exact["mask_gap"],
            exact["mask_firm_faults"], exact["masks"]) == (0.0, 0.0, 0.0, 1.0)
    assert exact["mask_fill"] == pytest.approx((prob >= 0.5).mean())
    # the whole ring of 0.51 read as 0: a gap, and no firm fault
    soft = _numbers([_record(prob >= 0.6)], monkeypatch)
    assert soft["mask_firm_faults"] == 0.0 and soft["mask_missing"] == 0.0
    assert 0.1 < soft["mask_gap"] < 0.4
    limits = dict(SPEC["config"]["correct"], records=1, masks=1)
    ok, compared = mcompare.judge(dict(exact, box_gap=0, score_gap=0,
                                       order_faults=0, low_scores=0,
                                       nms_faults=0), limits)
    assert ok and compared["mask_fill"][1] == [0.1, 0.9]


def test_one_firm_pixel_flipped_is_flagged(monkeypatch):
    _, prob = _reference_map()
    bits = prob >= 0.5
    bits[10, 10] = False                          # the disc's centre: 0.95
    got = _numbers([_record(bits)], monkeypatch)
    assert got["mask_firm_faults"] == pytest.approx(1 / 441)
    assert got["mask_missing"] == 0.0
    limits = {"mask_firm_faults": SPEC["config"]["correct"][
        "mask_firm_faults"]}
    assert mcompare.judge(got, limits)[0] is False
    # inside the margin the same flip is rounding's to make
    bits = prob >= 0.5
    bits[10, 16] = False                          # the ring: 0.51
    assert _numbers([_record(bits)], monkeypatch)["mask_firm_faults"] == 0.0


@pytest.mark.parametrize("fault", ["size", "sum", "outside", "absent",
                                   "counts-not-numbers"])
def test_a_malformed_mask_is_missing(fault, monkeypatch):
    _, prob = _reference_map()
    rec = _record(prob >= 0.5)
    if fault == "size":
        rec["segmentation"]["size"] = [W, H]
    elif fault == "sum":
        rec["segmentation"]["counts"][-1] += 1
    elif fault == "outside":
        frame = np.zeros((H, W), bool)
        frame[2, 3] = True                        # a pixel off the window
        rec = _record(prob >= 0.5, frame=frame)
    elif fault == "absent":
        del rec["segmentation"]
    else:
        rec["segmentation"]["counts"] = "0a1b"
    got = _numbers([rec, _record(prob >= 0.5)], monkeypatch)
    assert got["mask_missing"] == 1.0 and got["masks"] == 1.0
    assert mcompare.judge(got, {"mask_missing": 0})[0] is False


def test_empty_or_full_masks_are_out_of_range_whatever_computed_them(
        monkeypatch):
    monkeypatch.setattr(mcompare.mrcnn_fpn, "masks",
                        lambda d, boxes, labels, net: [
                            ((10, 5), np.full((21, 21), 0.01))
                            for _ in boxes])
    monkeypatch.setattr(mcompare.boxes_compare, "compare",
                        lambda sample, dense, net: {"records": 1.0})
    got = mcompare.compare(
        [{"doc": {}, "detections": [_record(np.zeros((21, 21), bool))]}],
        [{"prob": None, "boxes": None, "hw": (H, W)}], NET)
    assert got["mask_gap"] == 0.0 and got["mask_fill"] == 0.0
    assert mcompare.judge(got, {"mask_gap": 0.1,
                                "mask_fill": [0.1, 0.9]})[0] is False


def test_the_rle_the_control_writes_is_the_programs():
    from mx_rcnn_tpu.eval.mask_rle import encode

    rng = np.random.default_rng(4)
    for bits in (rng.random((7, 5)) > 0.5, np.ones((3, 4), bool),
                 np.zeros((3, 4), bool)):
        ours = control.encode_mask(bits)
        assert ours == encode(bits.astype(np.uint8))
        np.testing.assert_array_equal(
            mcompare.decode_counts(ours["counts"], *bits.shape), bits)


# ------------------------------------------------- the files' own statements

def test_the_configuration_states_what_the_issue_asks():
    c = SPEC["config"]
    fpn = harness.load_cell("fpn-serve-closed")["config"]
    assert c["network"] == "resnet101_fpn_mask" and c["dataset"] == "coco"
    assert (c["cfg"], c["serve_flags"], c["batch_per_chip"], c["precision"]) \
        == (fpn["cfg"], fpn["serve_flags"], 8, fpn["precision"])
    assert c["reduced"] == [] and c["architecture"] is None
    assert "1703.06870" in c["source"] and "mask_rcnn_R-101-FPN" in c["source"]
    assert len(c["source"]) < 200
    assert c["assumed"][:len(fpn["assumed"])] == fpn["assumed"]
    assert len(c["assumed"]) >= len(fpn["assumed"]) + 5
    assert {k: v for k, v in NET.items() if not k.startswith("mask_")} \
        == fpn["net"]
    assert (NET["mask_pooled"], NET["mask_samples"], NET["mask_convs"],
            NET["mask_channels"], NET["mask_size"], NET["mask_thresh"]) == (
        14, 2, 4, 256, 28, 0.5)
    assert 0 < NET["mask_margin"] < 0.1
    assert c["modules"] == {
        "weights": "benchmark.mask.weights",
        "reference": "benchmark.reference.mrcnn_fpn",
        "compare": "benchmark.mask.compare", "flops": "benchmark.mask.flops",
        "control": "benchmark.mask.control"}
    box_half = {k: v for k, v in c["correct"].items()
                if not k.startswith("mask")}
    assert box_half == fpn["correct"]
    assert set(c["correct"]) - set(box_half) == {
        "masks", "mask_missing", "mask_gap", "mask_firm_faults", "mask_fill"}
    assert c["correct"]["mask_missing"] == 0
    # the program's defaults are the ones the reference is told
    from mx_rcnn_tpu.config import generate_config

    cfg = generate_config("resnet101_fpn_mask", "coco")
    assert cfg.network.HAS_MASK and cfg.TEST.MASK_PASTE == "native"
    assert (cfg.TEST.MAX_PER_IMAGE, cfg.TRAIN.MASK_SIZE) == (
        NET["test_max_per_image"], NET["mask_size"])


def test_the_cell_is_the_pyramid_cells_traffic_on_one_chip():
    assert SPEC["cell"] == dict(SPEC["cell"], config="r101-fpn-mask",
                                traffic="closed-16-coco", chips=1)
    assert SPEC["traffic"] == harness.load_cell("fpn-serve-closed")["traffic"]
    bench = SPEC["bench"]
    e2e = [m["name"] for m in harness.metrics_of(bench, "end_to_end",
                                                 "mask-serve-closed")]
    assert e2e == ["setup_s", "serve_imgs_per_s"]
    mine = [m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                  "mask-serve-closed")]
    theirs = [m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                    "fpn-serve-closed")]
    # every metric of the pyramid cell but the two RoI counters, whose
    # list an existing test pins (PERF.md section 7 row 11), then the five
    assert mine == [n for n in theirs if n not in (
        "rois_valid_per_img", "roi_p2_share")] + [
        "turn_mask_ms", "turn_mask_paste_ms", "mask_device_ms", "mask_mfu",
        "masks_per_img"]
    assert [c["name"] for c in bench["configs"]][-1] == "r101-fpn-mask"
    assert [w["name"] for w in bench["workloads"]][-1] == "mask-serve-closed"
    assert harness.programs_marker(SPEC) != harness.programs_marker(
        harness.load_cell("fpn-serve-closed"))


def test_the_parent_ends_at_once_on_the_new_cell(tmp_path):
    bench = json.loads(json.dumps(SPEC["bench"]))
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] != "mask-serve-closed"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(SystemExit, match="no workload 'mask-serve-closed'"):
        harness.load_cell("mask-serve-closed", root=str(tmp_path))
    assert os.path.exists(os.path.join(harness.HERE, "configs",
                                       "r101-fpn-mask.json"))
