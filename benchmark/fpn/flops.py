"""``r101-fpn``'s operations and bytes, counted from shapes alone: the
``flops`` row of ``benchmark/README.md``, "A configuration".  From the plain
reference's list of layers (``reference.frcnn_fpn.conv_layers``) and the
configuration's sizes, never from the program under test or the compiler's
cost analysis.

A multiply-accumulate is two operations.  Counted: every conv of the trunk
(C2..C5) and of the neck on its own level's map, the shared RPN head on each
of the five levels, the four fc layers of the head once a RoI.  **Not
counted**, whatever implements it: RoIAlign's interpolation (vector work by
the algorithm; a program that spends MXU time on it — a dense contraction
over the whole map — does not raise ``predict_mfu`` by it), BN affines,
ReLU, max-pool, upsampling, softmax, box decoding, NMS.
"""

from __future__ import annotations

import math

from benchmark.flops import conv_macs, nms_work, roofline_seconds  # noqa: F401
from benchmark.reference.frcnn_fpn import conv_layers


def level_sizes(net: dict) -> dict:
    """{level: (h, w)} of P2..P6 at the bucket's padded size."""
    stride = net["image_stride"]
    h = math.ceil(net["scale"][0] / stride) * stride
    w = math.ceil(net["scale"][1] / stride) * stride
    out = {lvl: (h // 2 ** lvl, w // 2 ** lvl) for lvl in (2, 3, 4, 5)}
    out[6] = ((out[5][0] + 1) // 2, (out[5][1] + 1) // 2)   # P5[::2, ::2]
    return out


def predict_flops_per_image(net: dict) -> dict:
    """{"trunk", "neck", "rpn", "head", "total"}: FLOPs of one image's
    inference at the bucket's padded size with the test-time number of
    RoIs."""
    sizes = level_sizes(net)
    macs = {"trunk": 0, "neck": 0, "rpn": 0, "head": 0}
    th, tw = sizes[2][0] * 4, sizes[2][1] * 4     # trunk's running size
    for (path, kh, kw, cin, cout, s, _bn, _b, part) in conv_layers(
            net["depth"], net["num_classes"], net["num_anchors"],
            net["fpn_channels"], net["head_hidden"]):
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
        else:                                      # head_fc, a RoI
            macs["head"] += cin * cout * net["test_post_nms"]
    out = {k: 2 * v for k, v in macs.items()}
    out["total"] = sum(out.values())
    return out
