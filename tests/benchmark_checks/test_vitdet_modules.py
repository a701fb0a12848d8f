"""``vitdet-b-mask``'s own modules and files: the weights' list of leaves
against the program's tree at the published widths, every seed the same
network in another order, the operation counts against a count by hand, the
configuration's statements, the cell, and the parent's clean end on it."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import mrcnn_vitdet
from benchmark.vitdet import flops, weights

from . import tiny_vitdet

SPEC = harness.load_cell("vitdet-serve-closed")
CONFIG = SPEC["config"]
NET = CONFIG["net"]


def test_weights_are_the_programs_tree_at_the_published_widths():
    """Names and shapes only (nothing is drawn, nothing compiled): the
    reference's lists against the program's ViT-B parameter tree at the
    served 1024 x 1024."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models.detector import build_model, init_params

    cfg = generate_config("vitdet_b_mask", "coco")
    model = build_model(cfg)
    theirs = {"/".join(str(k.key) for k in path): tuple(s.shape)
              for path, s in jax.tree_util.tree_flatten_with_path(
                  jax.eval_shape(lambda: init_params(
                      model, cfg, jax.random.PRNGKey(0))))[0]}
    ours = {p: s for p, s, _ in weights.leaf_specs(NET)}
    assert ours == theirs
    assert ours["backbone/patch_embed/kernel"] == (16, 16, 3, 768)
    assert ours["backbone/pos_embed"] == (1, 64, 64, 768)
    assert ours["backbone/block0/attn/rel_pos_h"] == (27, 64)    # window 14
    assert ours["backbone/block2/attn/rel_pos_w"] == (127, 64)   # global
    assert ours["backbone/block11/attn/qkv/kernel"] == (768, 2304)
    assert ours["backbone/block7/fc1/kernel"] == (768, 3072)
    assert ours["neck/p2_deconv2/kernel"] == (2, 2, 384, 192)
    assert ours["neck/lateral2/kernel"] == (1, 1, 192, 256)
    assert "neck/lateral3/bias" not in ours and "neck/p3_deconv/bias" in ours
    assert ours["rpn/rpn_conv_3x3_2/kernel"] == (3, 3, 256, 256)
    assert ours["head_body/fc6/kernel"] == (7 * 7 * 256, 1024)
    assert ours["mask_head/mask_conv4_norm/scale"] == (256,)
    assert "mask_head/mask_conv1/bias" not in ours
    n = sum(int(np.prod(s)) for s in ours.values())
    assert 113e6 < n < 115e6      # ViT-B's 86 M + pyramid and heads


def test_every_seed_is_the_same_network_in_another_order():
    """Tiny: two seeds' arrays differ and hold the same values; the
    reference's trunk, pyramid and RPN give the same maps on both, to the
    order of summation; the same seed gives the same arrays, and seeds pass
    2**31."""
    net = tiny_vitdet.tiny_spec()["config"]["net"]
    a, b = weights.make(net, 1), weights.make(net, 2 ** 31 + 9)
    assert set(a) == {p for p, _, _ in weights.leaf_specs(net)}
    assert all(v.dtype == np.float32 for v in a.values())
    for k in ("backbone/pos_embed", "backbone/block2/attn/qkv/kernel",
              "backbone/block1/fc2/kernel", "backbone/block3/norm2/scale",
              "neck/post3/kernel", "rpn/rpn_conv_3x3_2/bias",
              "head_body/conv2/kernel", "mask_head/mask_deconv/kernel"):
        assert not np.array_equal(a[k], b[k]), k
        np.testing.assert_array_equal(np.sort(np.ravel(a[k])),
                                      np.sort(np.ravel(b[k])))
    x = jax.numpy.asarray(np.random.default_rng(0).uniform(
        -1, 1, (1, 96, 96, 3)).astype(np.float32))
    v = mrcnn_vitdet.static_vit(net)
    fa, ha = mrcnn_vitdet.pyramid_and_rpn(a, x, v=v, rpn_convs=2)
    fb, hb = mrcnn_vitdet.pyramid_and_rpn(b, x, v=v, rpn_convs=2)
    for (la, da), (lb, db) in zip(ha, hb):
        np.testing.assert_allclose(la, lb, atol=2e-4)
        np.testing.assert_allclose(da, db, atol=2e-5)
    # the pyramid's channels come in another order, the same numbers
    np.testing.assert_allclose(np.sort(np.asarray(fa[1]), -1),
                               np.sort(np.asarray(fb[1]), -1), atol=2e-4)
    # the mask head's kernels sum to zero over their inputs
    assert abs(float(a["mask_head/mask_out/kernel"].sum(2).max())) < 1e-5
    again = weights.make(net, 2 ** 31 + 9)
    assert all(np.array_equal(b[k], again[k]) for k in b)


def test_a_program_without_the_network_ends_the_run_at_once(monkeypatch):
    import mx_rcnn_tpu.config as program_config

    monkeypatch.setattr(program_config, "list_networks",
                        lambda: ["resnet101_fpn_mask"])
    with pytest.raises(SystemExit, match="no network 'vitdet_b_mask'"):
        weights.make(NET, 1)


def test_predict_flops_by_hand_at_the_published_sizes():
    """ISSUE 34's sums, in multiply-accumulates an image at 1024 x 1024."""
    G = 1e9
    got = {k: v / 2 for k, v in flops.predict_flops_per_image(NET).items()}
    assert got["patch_embed"] == 4096 * 16 * 16 * 3 * 768
    # a windowed block: qkv + proj on the padded 70 x 70 grid, the MLP on
    # 64 x 64, attention inside 25 windows of 196 tokens
    win_attn = 25 * 12 * (2 * 196 * 196 * 64 + 2 * 196 * 14 * 64)
    window = 4900 * 768 * (2304 + 768) + 4096 * 768 * 3072 * 2 + win_attn
    glob_attn = 12 * (2 * 4096 * 4096 * 64 + 2 * 4096 * 64 * 64)
    glob = 4096 * 768 * (2304 + 768) + 4096 * 768 * 3072 * 2 + glob_attn
    assert got["block_window"] == 8 * window
    assert got["block_global"] == 4 * glob
    assert got["attention"] == 8 * win_attn + 4 * glob_attn
    assert window / G == pytest.approx(32.4, abs=0.1)
    assert glob / G == pytest.approx(55.2, abs=0.1)
    assert glob_attn / G == pytest.approx(26.2, abs=0.05)
    trunk = got["patch_embed"] + got["block_window"] + got["block_global"]
    assert trunk / G == pytest.approx(482, abs=1.5)
    assert 0.23 < got["attention"] / trunk < 0.25
    sfp = (4096 * 4 * 768 * 384 + 128 * 128 * 4 * 384 * 192     # P2's deconvs
           + 4096 * 4 * 768 * 384                               # P3's
           + 256 * 256 * 192 * 256 + 128 * 128 * 384 * 256      # 1x1s
           + 64 * 64 * 768 * 256 + 32 * 32 * 768 * 256
           + (256 ** 2 + 128 ** 2 + 64 ** 2 + 32 ** 2) * 9 * 256 * 256)
    assert got["sfp"] == sfp and sfp / G == pytest.approx(72, abs=1)
    cells = 256 ** 2 + 128 ** 2 + 64 ** 2 + 32 ** 2 + 16 ** 2
    assert got["rpn"] == cells * (2 * 9 * 256 * 256 + 256 * 18)
    assert got["rpn"] / G == pytest.approx(103, abs=1)
    head = 4 * 49 * 9 * 256 * 256 + 12544 * 1024 + 1024 * (81 + 324)
    assert got["head"] == 1000 * head and head / 1e6 == pytest.approx(
        128.9, abs=0.2)
    total = flops.predict_flops_per_image(NET)["total"]
    assert total == 2 * (trunk + sfp + got["rpn"] + got["head"])
    assert total / 1e12 == pytest.approx(1.57, abs=0.01)


def test_attn_work_is_the_mathematics_of_the_global_blocks():
    work = flops.attn_work(NET)
    assert work["ops"] == 4 * 12 * 2 * (2 * 4096 * 4096 * 64)
    assert work["bytes"] == 4 * 12 * 4 * 4096 * 64 * 2
    peaks = harness.peaks_for("TPU v5 lite")
    least, bound = flops.roofline_seconds(work["ops"], work["bytes"], peaks)
    assert bound == "compute" and least == pytest.approx(1.0466e-3, rel=1e-3)
    # the general functions are the accepted ones
    from benchmark import flops as c4_flops
    assert flops.nms_work is c4_flops.nms_work
    assert flops.roofline_seconds is c4_flops.roofline_seconds
    # the mask head a RoI: r101-fpn-mask's count (LN is not counted)
    from benchmark.mask import flops as mask_flops
    assert flops.mask_flops_per_image(NET, 100) == \
        mask_flops.mask_flops_per_image(dict(NET, depth="resnet101"), 100)


def test_the_configuration_states_what_the_issue_asks():
    assert CONFIG["network"] == "vitdet_b_mask" and CONFIG["dataset"] == "coco"
    assert CONFIG["architecture"] is None and CONFIG["reduced"] == []
    assert "2203.16527" in CONFIG["source"] and len(CONFIG["source"]) <= 200
    assert "tpu__SCALES=((1024,1024),)" in CONFIG["cfg"]
    mask = harness.load_cell("mask-serve-closed")["config"]
    assert CONFIG["cfg"][1:] == mask["cfg"][1:]          # the two test counts
    assert CONFIG["serve_flags"] == mask["serve_flags"]
    assert CONFIG["batch_per_chip"] == 8
    assert NET["vit"] == {"patch": 16, "width": 768, "depth": 12,
                          "heads": 12, "mlp_ratio": 4, "window": 14,
                          "global_blocks": [2, 5, 8, 11]}
    assert (NET["scale"], NET["fpn_channels"], NET["test_post_nms"],
            NET["test_max_per_image"], NET["test_pre_nms"]) == (
        [1024, 1024], 256, 1000, 100, 4768)
    assert (NET["test_nms"], NET["test_thresh"]) == (
        mask["net"]["test_nms"], mask["net"]["test_thresh"]) == (0.3, 0.001)
    assert CONFIG["modules"] == {
        "weights": "benchmark.vitdet.weights",
        "reference": "benchmark.reference.mrcnn_vitdet",
        "compare": "benchmark.vitdet.compare",
        "flops": "benchmark.vitdet.flops",
        "control": "benchmark.vitdet.control"}
    assert set(CONFIG["correct"]) == set(mask["correct"])
    assert len(CONFIG["correct"]) == 11
    said = " ".join(CONFIG["assumed"])
    for words in ("catalog", "RoIAlign", "81 class", "4768", "0.5 and 0.05",
                  "pos_embed", "PIXEL_STDS", "RLE", "weights from the seed"):
        assert words in said, words
    # the two kernels' names part the program's custom calls between them
    import re
    names = CONFIG["names"]
    nms, attn = re.compile(names["nms_kernel"]), re.compile(
        names["attn_kernel"])
    assert names["predict_program"] == mask["names"]["predict_program"]
    assert names["mask_program"] == mask["names"]["mask_program"]
    for op, which in (("closed_call.19 custom-call", "nms"),
                      ("closed_call.20 custom-call", "nms"),
                      ("vit_global_attention.4 custom-call", "attn"),
                      ("vit_global_attention.7 custom-call", "attn"),
                      ("custom-call.92 custom-call", None),
                      ("fusion.7", None)):
        assert bool(nms.search(op)) == (which == "nms"), op
        assert bool(attn.search(op)) == (which == "attn"), op
    assert re.search(mask["names"]["nms_kernel"],
                     "vit_global_attention.4 custom-call")   # why it changed


def test_the_kernels_name_is_the_programs():
    from mx_rcnn_tpu.kernels.attention_pallas import KERNEL_NAME
    assert CONFIG["names"]["attn_kernel"].startswith("^" + KERNEL_NAME)


def test_the_cell_is_the_mask_cells_traffic_on_one_chip():
    assert SPEC["cell"] == dict(SPEC["cell"], config="vitdet-b-mask",
                                traffic="closed-16-coco", chips=1)
    assert SPEC["traffic"] == harness.load_cell("mask-serve-closed")["traffic"]
    bench = SPEC["bench"]
    e2e = [m["name"] for m in harness.metrics_of(bench, "end_to_end",
                                                 "vitdet-serve-closed")]
    assert e2e == ["setup_s", "serve_imgs_per_s"]
    mine = [m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                  "vitdet-serve-closed")]
    theirs = [m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                    "mask-serve-closed")]
    # every metric of the mask cell but the five mask-stage ones, whose
    # lists test_mask_layers.py pins, then the two this PR adds
    assert mine == theirs[:-5] + ["attn_device_ms", "attn_roofline.serve"]
    for name, layer in (("attn_device_ms", "trunk / attention"),
                        ("attn_roofline.serve", "kernels")):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == ["vitdet-serve-closed"]
        assert (m["source"], m["moves"], m["layer"]) == (
            "device_trace", "serve_imgs_per_s", layer)
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) == 0
    assert harness.programs_marker(SPEC) != harness.programs_marker(
        harness.load_cell("mask-serve-closed"))
    assert len(bench["workloads"]) == 5 and len(bench["configs"]) == 4


def test_the_parent_ends_at_once_on_the_new_cell(tmp_path):
    bench = json.loads(json.dumps(SPEC["bench"]))
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] != "vitdet-serve-closed"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(SystemExit, match="no workload 'vitdet-serve-closed'"):
        harness.load_cell("vitdet-serve-closed", root=str(tmp_path))
    assert os.path.exists(os.path.join(harness.HERE, "configs",
                                       "vitdet-b-mask.json"))
