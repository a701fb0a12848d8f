"""``r101-fpn``'s own modules and data at their published sizes, without a
server: the operation counts, the weights from the seed, the two readers on
canned ``/metrics`` documents, and what the configuration's and the cell's
files state."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import harness
from benchmark.fpn import flops, weights
from benchmark.reference import frcnn_fpn

from . import tiny_fpn

SPEC = harness.load_cell("fpn-serve-closed")
NET = SPEC["config"]["net"]


# ------------------------------------------------------------------- flops

def test_predict_flops_by_hand_at_the_published_sizes():
    """800 x 1344: levels of 200x336 .. 13x21; the stem by hand; the RPN
    head's three convs on every level; the head's four fc layers a RoI."""
    assert flops.level_sizes(NET) == {2: (200, 336), 3: (100, 168),
                                      4: (50, 84), 5: (25, 42), 6: (13, 21)}
    f = flops.predict_flops_per_image(NET)
    assert f["total"] == f["trunk"] + f["neck"] + f["rpn"] + f["head"]
    cells = sum(h * w for h, w in flops.level_sizes(NET).values())
    assert f["rpn"] == 2 * cells * (9 * 256 * 256 + 256 * 6 + 256 * 12)
    assert f["head"] == 2 * 1000 * (12544 * 1024 + 1024 * 1024
                                    + 1024 * 81 + 1024 * 324)
    lat = sum(h * w * c for (h, w), c in zip(
        list(flops.level_sizes(NET).values())[:4], (256, 512, 1024, 2048)))
    post = sum(h * w for h, w in list(flops.level_sizes(NET).values())[:4])
    assert f["neck"] == 2 * 256 * (lat + 9 * 256 * post)
    # the trunk is r101-c4's three stages + res5 on the whole image
    assert 0.55 < f["trunk"] / f["total"] < 0.57
    assert round(f["total"] / 1e9) == 581


def test_roialign_is_not_counted_whatever_implements_it():
    """The count is the reference's list of conv / fc layers and nothing
    else: no entry for the pooling, so MXU time spent on a dense pooling
    cannot raise ``predict_mfu``."""
    parts = {layer[-1] for layer in frcnn_fpn.conv_layers()}
    assert parts == {"trunk", "neck", "rpn", "head_fc"}
    one = dict(NET, test_post_nms=1)
    per_roi = (flops.predict_flops_per_image(NET)["head"]
               - flops.predict_flops_per_image(one)["head"]) / 999
    assert per_roi == 2 * (12544 * 1024 + 1024 * 1024 + 1024 * 405)


def test_the_joint_nms_contract_and_the_general_functions():
    assert NET["test_pre_nms"] == 4 * NET["test_pre_nms_per_level"] + 819
    assert flops.nms_work(4819, 1000)["ops"] == 16 * 4819 * 1000
    t, bound = flops.roofline_seconds(
        **{"ops": 1e12, "nbytes": 1.0}, peaks=harness.peaks_for("TPU v5 lite"))
    assert bound == "compute" and t == pytest.approx(1e12 / 197e12)


# ----------------------------------------------------------------- weights

@pytest.fixture(scope="module")
def tiny_weights():
    net = tiny_fpn.tiny_spec()["config"]["net"]
    return net, weights.make(net, 1), weights.make(net, 2 ** 31 + 9)


def test_fpn_weights_are_the_programs_tree_at_the_published_widths():
    """Names and shapes only (nothing is drawn): the reference's list of
    layers against the program's ResNet-101-FPN parameter tree."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models.detector import build_model, init_params

    cfg = generate_config("resnet101_fpn", "coco",
                          tpu__SCALES=((128, 192),))
    model = build_model(cfg)
    theirs = {"/".join(str(k.key) for k in path): tuple(s.shape)
              for path, s in jax.tree_util.tree_flatten_with_path(
                  jax.eval_shape(lambda: init_params(
                      model, cfg, jax.random.PRNGKey(0))))[0]}
    ours = {path: shape for path, shape, _ in weights.leaf_specs(NET)}
    assert ours == theirs
    assert ours["head_body/fc6/kernel"] == (12544, 1024)
    assert ours["rpn/rpn_cls_score/kernel"] == (1, 1, 256, 6)
    assert sum(int(np.prod(s)) for s in ours.values()) > 60e6


def test_every_seed_is_the_same_pyramid_network_in_another_order(
        tiny_weights):
    import jax.numpy as jnp

    net, a, b = tiny_weights
    for k in ("backbone/stage1/unit2/conv1/kernel", "neck/lateral3/bias",
              "neck/post4/kernel", "rpn/rpn_conv_3x3/bias",
              "head_body/fc7/kernel", "rcnn_out/cls_score/kernel"):
        assert not np.array_equal(a[k], b[k]), k
        np.testing.assert_array_equal(np.sort(np.ravel(a[k])),
                                      np.sort(np.ravel(b[k])))
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(1, 128, 192, 3)).astype(np.float32))
    fa, ha = frcnn_fpn.pyramid_and_rpn(a, x, depth="resnet50")
    fb, hb = frcnn_fpn.pyramid_and_rpn(b, x, depth="resnet50")
    for (la, da), (lb, db) in zip(ha, hb):
        np.testing.assert_allclose(la, lb, atol=2e-3)
        np.testing.assert_allclose(da, db, atol=2e-4)
    # the head too: the same crops give the same answers
    crops = jnp.asarray(np.random.default_rng(1).normal(
        size=(4, 7, 7, 256)).astype(np.float32))
    pa, da = frcnn_fpn.box_head(a, crops)
    pb, db = frcnn_fpn.box_head(b, crops)
    np.testing.assert_allclose(pa, pb, atol=1e-4)
    np.testing.assert_allclose(da, db, atol=1e-4)
    # P2..P5 themselves are permuted only where the neck's merged maps are:
    # their values as a multiset a cell are not (post's outputs are not
    # permuted), so they agree as they are
    for xa, xb in zip(fa, fb):
        np.testing.assert_allclose(xa, xb, atol=2e-3)


def test_fpn_weights_depend_on_the_seed_alone_and_seeds_may_pass_2_31(
        tiny_weights):
    net, _, b = tiny_weights
    again = weights.make(net, 2 ** 31 + 9)
    assert all(np.array_equal(b[k], again[k]) for k in b)
    assert all(v.dtype == np.float32 for v in b.values())


# ----------------------------------------------------------------- readers

def _ctx(before, after):
    return {"metrics_before": {"counters": before},
            "metrics_after": {"counters": after}}


def _read(ctx):
    bench = {"per_layer": [m for m in SPEC["bench"]["per_layer"]
                           if m["name"] in ("rois_valid_per_img",
                                            "roi_p2_share")]}
    assert len(bench["per_layer"]) == 2
    return {k: v["value"] for k, v in harness.read_layers(
        bench, "fpn-serve-closed", ctx).items()}


def test_the_roi_readers_on_canned_counters():
    before = {"served": 16, "rois_valid": 16000, "rois_level_p2": 8000}
    after = {"served": 96, "rois_valid": 94400, "rois_level_p2": 47200}
    assert _read(_ctx(before, after)) == {"rois_valid_per_img": 980.0,
                                          "roi_p2_share": 50.0}


@pytest.mark.parametrize("before, after", [
    ({"served": 1}, {"served": 9}),                        # the parent
    ({"served": 9, "rois_valid": 5, "rois_level_p2": 1},
     {"served": 9, "rois_valid": 5, "rois_level_p2": 1}),  # nothing served
    ({}, {}),
], ids=["a-program-without-the-counters", "an-empty-window", "no-counters"])
def test_the_roi_readers_find_nothing_and_do_not_raise(before, after):
    assert _read(_ctx(before, after)) == {}
    assert _read({"metrics_before": {}, "metrics_after": {}}) == {}


# ------------------------------------------------- the files' own statements

def test_the_configuration_states_what_the_issue_asks():
    c = SPEC["config"]
    assert c["network"] == "resnet101_fpn" and c["dataset"] == "coco"
    assert c["cfg"] == ["tpu__SCALES=((800,1344),)",
                        "TEST__RPN_PRE_NMS_TOP_N=5000",
                        "TEST__RPN_POST_NMS_TOP_N=1000"]
    assert c["serve_flags"] == ["--synthetic", "--serve-batch", "8",
                                "--max-delay-ms", "10", "--max-queue", "64"]
    assert c["batch_per_chip"] == 8 and c["reduced"] == []
    assert "1612.03144" in c["source"] and "R-101-FPN" in c["source"]
    assert NET["strides"] == [4, 8, 16, 32, 64]
    assert NET["anchor_sizes"] == [NET["anchor_scale"] * s
                                   for s in NET["strides"]]
    assert (NET["num_classes"], NET["num_anchors"], NET["fpn_channels"],
            NET["head_hidden"]) == (81, 3, 256, 1024)
    assert NET["test_post_nms"] == 1000 and NET["scale"] == [800, 1344]
    assert len(c["assumed"]) >= 6 and all(len(a) < 400 for a in c["assumed"])
    # the program's defaults are the ones the reference is told
    from mx_rcnn_tpu.config import generate_config

    cfg = generate_config("resnet101_fpn", "coco")
    assert (cfg.TEST.NMS, cfg.TEST.THRESH, cfg.TEST.MAX_PER_IMAGE,
            cfg.TEST.RPN_NMS_THRESH, cfg.TEST.RPN_MIN_SIZE) == (
        NET["test_nms"], NET["test_thresh"], NET["test_max_per_image"],
        NET["rpn_nms_thresh"], NET["rpn_min_size"])
    assert cfg.tpu.ROI_SAMPLING_RATIO == NET["roi_samples"] == 2
    assert list(cfg.network.FPN_FEAT_STRIDES) == NET["strides"]


def test_the_cells_traffic_is_the_c4_closed_mix_with_16_clients():
    mix = SPEC["traffic"]
    c4 = harness.load_cell("c4-serve-closed")["traffic"]
    assert mix["clients"] == 16 == 2 * SPEC["config"]["batch_per_chip"]
    assert {k: v for k, v in mix.items() if k not in ("clients", "notes")} \
        == {k: v for k, v in c4.items() if k not in ("clients", "notes")}
    assert SPEC["cell"]["chips"] == 1
    bench = SPEC["bench"]
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) == 0
    mine = [m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                  "fpn-serve-closed")]
    assert len(mine) == 27 and mine[-2:] == ["rois_valid_per_img",
                                             "roi_p2_share"]
    for name in ("rois_valid_per_img", "roi_p2_share"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == ["fpn-serve-closed"]
        assert m["moves"] == "serve_imgs_per_s"
        assert m["layer"] == "predictor / model"


def test_the_parent_ends_at_once_on_the_new_cell(tmp_path):
    """A checkout without the cell (the parent of this PR): ``load_cell``
    raises SystemExit naming the workload before anything is built."""
    bench = json.loads(json.dumps(SPEC["bench"]))
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] != "fpn-serve-closed"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(SystemExit, match="no workload 'fpn-serve-closed'"):
        harness.load_cell("fpn-serve-closed", root=str(tmp_path))
    assert os.path.exists(os.path.join(harness.HERE, "traffic",
                                       "closed-16-coco.json"))
