"""``benchmark/flops.py`` against hand-worked numbers."""

import pytest

from benchmark import flops, harness

NET = harness.load_cell("c4-serve-open")["config"]["net"]


def test_one_conv_by_hand():
    # 3x3, 64 -> 128 channels, 10x20 outputs: 9*64*128 MACs an output
    assert flops.conv_macs(10, 20, 3, 3, 64, 128) == 200 * 9 * 64 * 128


def test_stem_and_first_unit_by_hand():
    f = flops.predict_flops_per_image(dict(NET, depth="resnet50"))
    g = flops.predict_flops_per_image(NET)
    # ResNet-101 has 17 more stage-3 units than ResNet-50, each
    # 1x1 1024->256, 3x3 256->256, 1x1 256->1024 on the 38x64 map
    unit = 38 * 64 * (1024 * 256 + 9 * 256 * 256 + 256 * 1024)
    assert g["trunk"] - f["trunk"] == 2 * 17 * unit
    assert g["rpn"] == f["rpn"] == 2 * 38 * 64 * (9 * 1024 * 512
                                                  + 512 * 18 + 512 * 36)


def test_totals_are_in_the_published_range():
    g = flops.predict_flops_per_image(NET)
    # R101 conv1-conv4 is 6.9 GMAC at 224x224 -> x (608*1024)/(224*224)
    assert g["trunk"] == pytest.approx(2 * 6.9e9 * 608 * 1024 / 224 ** 2,
                                       rel=0.03)
    assert g["total"] == g["trunk"] + g["rpn"] + g["head"]
    assert g["head"] > g["trunk"]          # 300 RoIs through res5


def test_nms_count_and_roofline_bound():
    w = flops.nms_work(6000, 300)
    assert w["ops"] == 16 * 6000 * 300
    assert w["bytes"] == 6000 * 16 + 6000 * 4 + 300 * 5
    peaks = harness.peaks_for("TPU v5 lite")
    t, bound = flops.roofline_seconds(w["ops"], w["bytes"], peaks)
    assert bound == "memory" and t == pytest.approx(w["bytes"] / 819e9)
    t2, bound2 = flops.roofline_seconds(1e15, 1.0, peaks)
    assert bound2 == "compute" and t2 == pytest.approx(1e15 / 197e12)
