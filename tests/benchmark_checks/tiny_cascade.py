"""The tiny size the CPU checks of ``r101-fpn-cascade-mask`` run at:
``tiny_mask``'s cuts (ResNet-50 body, a 128x192 bucket, 418 -> 60
proposals, batch 2, 12 records an image) on the cascade network.  Tests may
cut sizes; the benchmark never does."""

import copy

from benchmark import harness

from . import tiny_mask

# tiny_mask's box and mask limits, a floor of 40 records (the sample may
# hold four requests where the largest body is among those drawn); the
# two on the stages from CPU runs at this size (test_cascade_rcnn_run.py's
# docstring has the readings)
LIMITS = dict(tiny_mask.LIMITS, records=40, stage1_box_iou=0.95,
              stage1_score_gap=0.2)

CFG = list(tiny_mask.CFG)


def tiny_spec(workload: str = "cascade-serve-closed",
              root: str = harness.ROOT) -> dict:
    spec = copy.deepcopy(harness.load_cell(workload, root=root))
    mask = tiny_mask.tiny_spec()
    c = spec["config"]
    c["cfg"] = list(CFG)
    c["serve_flags"] = mask["config"]["serve_flags"]
    c["batch_per_chip"] = 2
    c["net"].update(depth="resnet50", scale=[128, 192],
                    test_pre_nms_per_level=100, test_pre_nms=418,
                    test_post_nms=60, test_max_per_image=12,
                    mask_margin=0.02)
    c["correct"] = copy.deepcopy(LIMITS)
    spec["traffic"] = mask["traffic"]
    return spec
