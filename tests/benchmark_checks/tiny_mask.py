"""The tiny size the CPU checks of ``r101-fpn-mask`` run at: ``tiny_fpn``'s
cuts (ResNet-50 body, a 128x192 bucket, 418 -> 60 proposals, batch 2) on the
mask network, 12 records an image.  Tests may cut sizes; the benchmark
never does."""

import copy

from benchmark import harness

from . import tiny_fpn

# the box half is tiny_fpn's; the mask half from CPU runs at this size
# (test_mask_run.py's docstring has the readings): mask_gap 0.046 against
# the float8 control's 0.31-0.33, firm faults at a margin of 0.02 3e-5
# against 0.051-0.054
LIMITS = dict(tiny_fpn.LIMITS, masks=40, mask_missing=0, mask_gap=0.12,
              mask_firm_faults=0.002, mask_fill=[0.1, 0.9])

CFG = tiny_fpn.CFG + ["network__NETWORK='resnet50'",
                      "TEST__MAX_PER_IMAGE=12"]


def tiny_spec(workload: str = "mask-serve-closed",
              root: str = harness.ROOT) -> dict:
    spec = copy.deepcopy(harness.load_cell(workload, root=root))
    fpn = tiny_fpn.tiny_spec()
    c = spec["config"]
    c["cfg"] = list(CFG)
    c["serve_flags"] = fpn["config"]["serve_flags"]
    c["batch_per_chip"] = 2
    c["net"].update(depth="resnet50", scale=[128, 192],
                    test_pre_nms_per_level=100, test_pre_nms=418,
                    test_post_nms=60, test_max_per_image=12,
                    mask_margin=0.02)
    c["correct"] = copy.deepcopy(LIMITS)
    spec["traffic"] = fpn["traffic"]
    return spec
