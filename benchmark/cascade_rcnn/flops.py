"""``r101-fpn-cascade-mask``'s operations, counted from shapes alone: the
``flops`` row of ``benchmark/README.md``, "A configuration".  From the
plain reference's list of layers (``reference.cmrcnn_fpn.conv_layers``)
and the configuration's sizes, never from the program under test or the
compiler's cost analysis.  A multiply-accumulate is two operations.

* ``predict_flops_per_image``: **stage 1's program only** — the trunk, the
  neck, the RPN on five levels and stage 1's head a RoI (its box output 4
  wide) — since ``predict_mfu`` divides it by the time of ``^jit_f``, the
  predict program, which runs nothing of stages 2 and 3.  RoIAlign's
  interpolation is not counted there, as ``benchmark.fpn.flops`` argues.
* ``roi_cascade_work``: what stages 2.. require an image, as
  ``roi_cascade_mfu`` credits the ``jit_roi_cascade`` program with: each
  stage's two FC-1024 and two output layers a RoI, and its RoIAlign on the
  **one** level a RoI is assigned (7 x 7 bins x 2 x 2 samples, each sample
  four taps of a multiply-add a channel).  A program that pools every RoI
  on all four levels' whole maps spends work the architecture does not
  require: it is not credited.  Decoding and clipping are not counted.
* ``mask_flops_per_image``: ``benchmark.mask.flops``'s, as it is.
"""

from __future__ import annotations

from benchmark.fpn.flops import (conv_macs, level_sizes, nms_work,  # noqa: F401
                                 roofline_seconds)
from benchmark.mask.flops import mask_flops_per_image  # noqa: F401
from benchmark.reference.cmrcnn_fpn import conv_layers


def layers_of(net: dict):
    return conv_layers(net["depth"], net["num_classes"], net["num_anchors"],
                       net["fpn_channels"], net["head_hidden"],
                       net["cascade_stages"])


def predict_flops_per_image(net: dict) -> dict:
    """{"trunk", "neck", "rpn", "head", "total"}: FLOPs of one image in the
    predict program (stage 1) at the bucket's padded size with the
    test-time number of RoIs."""
    sizes = level_sizes(net)
    macs = {"trunk": 0, "neck": 0, "rpn": 0, "head": 0}
    th, tw = sizes[2][0] * 4, sizes[2][1] * 4     # trunk's running size
    for (path, kh, kw, cin, cout, s, _bn, _b, part) in layers_of(net):
        if part == "trunk":
            oh, ow = th // s, tw // s
            macs["trunk"] += conv_macs(oh, ow, kh, kw, cin, cout)
            if path == "backbone/conv1":
                th, tw = oh // 2, ow // 2          # the 3x3/2 max-pool
            elif path.endswith("conv2"):           # the strided conv of a unit
                th, tw = oh, ow
        elif part == "neck":
            lh, lw = sizes[int(path[-1])]
            macs["neck"] += conv_macs(lh, lw, kh, kw, cin, cout)
        elif part == "rpn":
            macs["rpn"] += sum(conv_macs(lh, lw, kh, kw, cin, cout)
                               for lh, lw in sizes.values())
        elif part == "head_fc":                    # stage 1, a RoI
            macs["head"] += cin * cout * net["test_post_nms"]
    out = {k: 2 * v for k, v in macs.items()}
    out["total"] = sum(out.values())
    return out


def roi_align_flops_per_roi(net: dict) -> int:
    """One RoI pooled 7 x 7 on its one level: every bin's samples, four
    bilinear taps each, a multiply-add a channel."""
    bins = net["roi_pooled"] ** 2 * net["roi_samples"] ** 2
    return 2 * 4 * bins * net["fpn_channels"]


def roi_cascade_work(net: dict) -> dict:
    """{"head", "pool", "total"}: FLOPs stages 2.. require for one image's
    ``test_post_nms`` RoIs."""
    rois = net["test_post_nms"]
    head = sum(2 * cin * cout for (_p, _kh, _kw, cin, cout, _s, _bn, _b,
                                   part) in layers_of(net)
               if part == "stage_fc")
    pool = (net["cascade_stages"] - 1) * roi_align_flops_per_roi(net)
    out = {"head": head * rois, "pool": pool * rois}
    out["total"] = out["head"] + out["pool"]
    return out
