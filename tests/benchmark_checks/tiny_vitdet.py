"""The tiny size the CPU checks of ``vitdet-b-mask`` run at: the cell's own
spec with every size cut — a trunk of 4 blocks of width 64 with 2 heads of
32, block 2 global and the rest windowed (window 4 on a 6 x 6 grid, padded
to 8 x 8: 4 windows), a 96 x 96 bucket (P2 24 x 24 .. P5 3 x 3, P6 2 x 2
with 12 anchors), 339 -> 60 proposals, 12 records an image, batch 2.  Tests
may cut sizes; the benchmark never does."""

import ast
import copy

from benchmark import harness

# set from CPU runs at this size (test_vitdet_run.py's docstring has the
# readings)
LIMITS = {"records": 40, "box_gap": 0.015, "score_gap": 0.07,
          "order_faults": 0, "low_scores": 0, "nms_faults": 0,
          "masks": 40, "mask_missing": 0, "mask_gap": 0.08,
          "mask_firm_faults": 0.01, "mask_fill": [0.1, 0.9]}

VIT = {"patch": 16, "width": 64, "depth": 4, "heads": 2, "mlp_ratio": 4,
       "window": 4, "global_blocks": [2]}

CFG = ["tpu__SCALES=((96,96),)", "network__VIT_WIDTH=64",
       "network__VIT_DEPTH=4", "network__VIT_HEADS=2",
       "network__VIT_WINDOW=4", "network__VIT_GLOBAL_BLOCKS=(2,)",
       "TEST__RPN_PRE_NMS_TOP_N=500", "TEST__RPN_POST_NMS_TOP_N=60",
       "TEST__MAX_PER_IMAGE=12"]


def tiny_spec(workload: str = "vitdet-serve-closed",
              root: str = harness.ROOT) -> dict:
    spec = copy.deepcopy(harness.load_cell(workload, root=root))
    c = spec["config"]
    c["cfg"] = list(CFG)
    c["serve_flags"] = ["--synthetic", "--serve-batch", "2",
                        "--max-delay-ms", "10", "--max-queue", "64"]
    c["batch_per_chip"] = 2
    c["net"].update(vit=dict(VIT), scale=[96, 96],
                    test_pre_nms_per_level=100, test_pre_nms=339,
                    test_post_nms=60, test_max_per_image=12,
                    mask_margin=0.02)
    c["correct"] = copy.deepcopy(LIMITS)
    t = spec["traffic"]
    t["clients"] = 4
    t["bodies"] = {"pool": 8, "short": [60, 80], "long": [90, 120],
                   "portrait_every": 4}
    t["warm_per_orientation"] = 2
    t["sample"] = 4
    return spec


def program_cfg(**overrides):
    """The program's Config at the tiny size (``--synthetic``'s pixel
    statistics included)."""
    from mx_rcnn_tpu.config import generate_config

    kw = {k: ast.literal_eval(v) for k, v in
          (item.split("=", 1) for item in CFG)}
    kw.update(network__PIXEL_STDS=(127.0,) * 3, **overrides)
    return generate_config("vitdet_b_mask", "coco", **kw)
