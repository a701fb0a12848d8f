"""``r101-fpn-cascade-mask``'s own modules and files: the weights' list of
leaves against the program's tree at the published widths, every seed the
same network in another order, each stage's box output at its own spread,
the operation counts against a count by hand, the configuration's
statements, the cell, and the parent's clean end on it."""

import json
import os
import re

import jax
import numpy as np
import pytest

from benchmark import harness
from benchmark.cascade_rcnn import flops, weights
from benchmark.reference import cmrcnn_fpn

from . import tiny_cascade

SPEC = harness.load_cell("cascade-serve-closed")
CONFIG = SPEC["config"]
NET = CONFIG["net"]
MASK = harness.load_cell("mask-serve-closed")


def test_weights_are_the_programs_tree_at_the_published_widths():
    """Names and shapes only (nothing drawn, nothing compiled): the
    reference's lists against the program's ResNet-101 cascade tree."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models.detector import build_model, init_params

    cfg = generate_config(weights.NETWORK, "coco", tpu__SCALES=((128, 192),))
    model = build_model(cfg)
    theirs = {"/".join(str(k.key) for k in path): tuple(s.shape)
              for path, s in jax.tree_util.tree_flatten_with_path(
                  jax.eval_shape(lambda: init_params(
                      model, cfg, jax.random.PRNGKey(0))))[0]}
    ours = {p: tuple(s) for p, s in weights.leaf_specs(NET)}
    assert ours == theirs
    for t, (body, head) in ((2, ("head_body2", "rcnn_out2")),
                            (3, ("head_body3", "rcnn_out3"))):
        assert cmrcnn_fpn.stage_names(t) == (body, head)
        assert ours[f"{body}/fc6/kernel"] == (7 * 7 * 256, 1024)
        assert ours[f"{head}/cls_score/kernel"] == (1024, 81)
        assert ours[f"{head}/bbox_pred/kernel"] == (1024, 4)
    assert ours["rcnn_out/bbox_pred/kernel"] == (1024, 4)
    assert ours["backbone/stage3/unit23/conv2/kernel"] == (3, 3, 256, 256)
    mask = {p: tuple(s) for p, s in
            harness.modules_of(MASK["config"])["weights"].leaf_specs(
                MASK["config"]["net"])}
    # r101-fpn-mask's tree, its box output 4 wide, and two more heads
    assert set(ours) - set(mask) == {
        f"{m}/{layer}/{leaf}" for m, layers in (
            ("head_body2", ("fc6", "fc7")), ("head_body3", ("fc6", "fc7")),
            ("rcnn_out2", ("cls_score", "bbox_pred")),
            ("rcnn_out3", ("cls_score", "bbox_pred")))
        for layer in layers for leaf in ("kernel", "bias")}
    assert {k for k in mask if mask[k] != ours.get(k)} == {
        "rcnn_out/bbox_pred/kernel", "rcnn_out/bbox_pred/bias"}


def test_every_seed_is_the_same_network_in_another_order():
    """Tiny: two seeds' stage heads differ and hold the same values, and
    give the same probabilities and deltas on the same crops; stage 1's box
    output is r101-fpn's class-1 columns at stage 1's spread; each later
    stage's deltas spread by 1 / w_t."""
    net = tiny_cascade.tiny_spec()["config"]["net"]
    a, b = weights.make(net, 1), weights.make(net, 2 ** 31 + 9)
    assert set(a) == {p for p, _ in weights.leaf_specs(net)}
    assert all(v.dtype == np.float32 for v in a.values())
    for k in ("head_body2/fc6/kernel", "head_body3/fc7/bias",
              "rcnn_out2/cls_score/kernel", "rcnn_out3/bbox_pred/kernel"):
        assert not np.array_equal(a[k], b[k]), k
        np.testing.assert_array_equal(np.sort(np.ravel(a[k])),
                                      np.sort(np.ravel(b[k])))
    crops = jax.numpy.asarray(np.random.default_rng(0).normal(
        size=(100, 7, 7, 256)).astype(np.float32))
    spread = {}
    for t in (1, 2, 3):
        pa, da = cmrcnn_fpn.box_head(a, crops, t=t)
        pb, db = cmrcnn_fpn.box_head(b, crops, t=t)
        np.testing.assert_allclose(pa, pb, atol=1e-5)
        np.testing.assert_allclose(da, db, atol=1e-6)
        spread[t] = np.asarray(da).std(0)
    w = np.asarray(NET["stage_weights"])
    for t in (2, 3):
        ratio = spread[t] / spread[2] * (w[t - 1] / w[1])
        np.testing.assert_allclose(ratio, 1.0, atol=0.25)
    from benchmark.fpn import weights as fpn_weights
    fpn = fpn_weights.make(net, 1)
    kern = np.asarray(a["rcnn_out/bbox_pred/kernel"])
    np.testing.assert_allclose(
        kern, np.asarray(fpn["rcnn_out/bbox_pred/kernel"])[:, 4:8]
        * weights.box_gains(net, 1) / 0.07, rtol=1e-5)
    again = weights.make(net, 2 ** 31 + 9)
    assert all(np.array_equal(b[k], again[k]) for k in b)


def test_a_program_without_the_network_ends_the_run_at_once(monkeypatch):
    import mx_rcnn_tpu.config as program_config

    monkeypatch.setattr(program_config, "list_networks",
                        lambda: ["resnet101_fpn_mask"])
    with pytest.raises(SystemExit,
                       match="no network 'resnet101_fpn_cascade_mask'"):
        weights.make(NET, 1)


def test_flops_by_hand_at_the_published_sizes():
    """ISSUE 40's sums: stage 1's program is r101-fpn's less the 320 box
    columns it no longer has; stages 2-3 are 27.96 MFLOP a RoI and stage
    of head, 56 GFLOP an image and 447 a batch of 8, the one-level
    RoIAlign under 1 GFLOP an image."""
    from benchmark.fpn import flops as fpn_flops

    mine = flops.predict_flops_per_image(NET)
    theirs = fpn_flops.predict_flops_per_image(dict(NET))
    assert mine["trunk"] == theirs["trunk"] and mine["rpn"] == theirs["rpn"]
    assert theirs["head"] - mine["head"] == 2 * 1000 * 1024 * 320
    assert mine["total"] == sum(v for k, v in mine.items() if k != "total")
    per_roi = 12544 * 1024 + 1024 * 1024 + 1024 * (81 + 4)
    assert per_roi == 13_980_672
    work = flops.roi_cascade_work(NET)
    assert work["head"] == 2 * 1000 * 2 * per_roi
    assert work["head"] / 1e9 == pytest.approx(55.92, abs=0.01)
    assert work["pool"] == 2 * 1000 * 2 * 4 * 49 * 4 * 256 < 1e9
    assert 8 * work["total"] / 1e9 == pytest.approx(453.8, abs=0.2)
    peaks = harness.peaks_for("TPU v5 lite")
    assert 8 * work["total"] / peaks["bf16_flops_per_s"] * 1e3 == \
        pytest.approx(2.30, abs=0.02)
    from benchmark.mask import flops as mask_flops
    assert flops.mask_flops_per_image is mask_flops.mask_flops_per_image
    assert flops.nms_work is fpn_flops.nms_work


def test_the_configuration_states_what_the_issue_asks():
    assert CONFIG["network"] == "resnet101_fpn_cascade_mask"
    assert CONFIG["dataset"] == "coco"
    assert CONFIG["architecture"] is None and CONFIG["reduced"] == []
    assert "1906.09756" in CONFIG["source"] and "CascadeROIHeads" in \
        CONFIG["source"] and len(CONFIG["source"]) <= 200
    mask = MASK["config"]
    for key in ("cfg", "serve_flags", "batch_per_chip", "precision",
                "dataset"):
        assert CONFIG[key] == mask[key], key
    assert {k: v for k, v in NET.items()
            if k not in ("cascade_stages", "stage_weights",
                         "cls_agnostic")} == mask["net"]
    assert NET["cascade_stages"] == 3 and NET["cls_agnostic"] is True
    assert NET["stage_weights"] == [[10, 10, 5, 5], [20, 20, 10, 10],
                                    [30, 30, 15, 15]]
    from mx_rcnn_tpu.config import generate_config
    prog = generate_config(CONFIG["network"], "coco").network
    assert prog.ROI_CASCADE_STAGES == NET["cascade_stages"]
    assert [list(w) for w in prog.ROI_CASCADE_WEIGHTS] == NET["stage_weights"]
    assert CONFIG["modules"] == {
        "weights": "benchmark.cascade_rcnn.weights",
        "reference": "benchmark.reference.cmrcnn_fpn",
        "compare": "benchmark.cascade_rcnn.compare",
        "flops": "benchmark.cascade_rcnn.flops",
        "control": "benchmark.cascade_rcnn.control"}
    assert set(CONFIG["correct"]) == set(mask["correct"]) | {
        "stage1_box_iou", "stage1_score_gap"}
    assert CONFIG["correct"]["stage1_box_iou"] < 1.0
    assert CONFIG["correct"]["stage1_score_gap"] > \
        CONFIG["correct"]["score_gap"]
    said = " ".join(CONFIG["assumed"])
    for words in ("depth 101", "0.5 and 0.05", "+1 box widths", "joint NMS",
                  "RoIAlign", "mmdetection", "folded", "weights from the seed",
                  "log(1000/16)"):
        assert words in said, words
    names = CONFIG["names"]
    assert names["roi_cascade_program"] == "^jit_roi_cascade"
    for key in ("predict_program", "mask_program", "nms_kernel"):
        assert names[key] == mask["names"][key]
    rx = {k: re.compile(v) for k, v in names.items()}
    for module, which in (("jit_fwd_wf", "predict_program"),
                          ("jit_roi_cascade", "roi_cascade_program"),
                          ("jit_mask_branch", "mask_program")):
        assert [k for k in ("predict_program", "roi_cascade_program",
                            "mask_program")
                if rx[k].search(module)] == [which], module


def test_the_cell_is_the_mask_cells_traffic_on_one_chip():
    assert SPEC["cell"] == dict(SPEC["cell"], config="r101-fpn-cascade-mask",
                                traffic="closed-16-coco", chips=1)
    assert SPEC["traffic"] == MASK["traffic"]
    bench = SPEC["bench"]
    assert [c["name"] for c in bench["configs"]][-1] == \
        "r101-fpn-cascade-mask"
    assert [w["name"] for w in bench["workloads"]][-1] == \
        "cascade-serve-closed"
    e2e = [m["name"] for m in harness.metrics_of(bench, "end_to_end",
                                                 "cascade-serve-closed")]
    assert e2e == ["setup_s", "serve_imgs_per_s"]
    mine = [m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                  "cascade-serve-closed")]
    # every metric that lists every other cell, then the two this cell adds
    every = [m["name"] for m in bench["per_layer"]
             if "workloads" not in m or all(
                 w["name"] in m["workloads"] for w in bench["workloads"])]
    assert mine == every + ["roi_cascade_device_ms", "roi_cascade_mfu"]
    assert "nms_roofline.serve" in mine and "predict_mfu" in mine
    for name in ("roi_cascade_device_ms", "roi_cascade_mfu"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == ["cascade-serve-closed"]
        assert (m["source"], m["moves"], m["layer"]) == (
            "device_trace", "serve_imgs_per_s", "RoI cascade")
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) == 0
    assert harness.programs_marker(SPEC) != harness.programs_marker(MASK)


def test_the_parent_ends_at_once_on_the_new_cell(tmp_path):
    """A parent's BENCHMARK.json has no such cell (the harness ends with
    no result); with this PR's benchmark files laid over it, the cell's
    weights end the run before anything is built (the test above)."""
    bench = json.loads(json.dumps(SPEC["bench"]))
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] != "cascade-serve-closed"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(SystemExit,
                       match="no workload 'cascade-serve-closed'"):
        harness.load_cell("cascade-serve-closed", root=str(tmp_path))
    assert os.path.exists(os.path.join(harness.HERE, "configs",
                                       "r101-fpn-cascade-mask.json"))
