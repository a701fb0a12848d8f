"""Operations and bytes that the work requires, counted from shapes alone:
from the plain reference's list of layers and the configuration's sizes,
never from the program under test or the compiler's cost analysis.

A multiply-accumulate is two operations.  Counted: every conv and fc of the
trunk, the RPN and the RoI head.  Not counted (vector work, under 1 % of the
total): BN affines, ReLU, max-pool, RoIAlign's interpolation, softmax, box
decoding, NMS.  Backward passes, where a later cell trains: twice the
forward of every layer that gets a gradient — stated there, not here.
"""

from __future__ import annotations

import math

from benchmark.reference.frcnn_c4 import conv_layers


def conv_macs(out_h: int, out_w: int, kh: int, kw: int, cin: int,
              cout: int) -> int:
    return out_h * out_w * kh * kw * cin * cout


def predict_flops_per_image(net: dict) -> dict:
    """{"trunk", "rpn", "head", "total"}: FLOPs of one image's inference at
    the bucket's padded size with the test-time number of RoIs."""
    stride = net["image_stride"]
    h = math.ceil(net["scale"][0] / stride) * stride
    w = math.ceil(net["scale"][1] / stride) * stride
    macs = {"trunk": 0, "rpn": 0, "head": 0}
    th, tw = h, w                  # trunk's running spatial size
    hh = hw = 14                   # head's running size (the RoI crop)
    for (path, kh, kw, cin, cout, s, _bn, _b, part) in conv_layers(
            net["depth"], net["num_classes"], net["num_anchors"]):
        if part == "trunk":
            if path == "backbone/conv1":
                th, tw = th // 2, tw // 2
                macs["trunk"] += conv_macs(th, tw, kh, kw, cin, cout)
                th, tw = th // 2, tw // 2          # the 3x3/2 max-pool
                continue
            oh, ow = (th // s, tw // s)
            macs["trunk"] += conv_macs(oh, ow, kh, kw, cin, cout)
            if path.endswith("conv2"):             # the strided conv of a unit
                th, tw = oh, ow
        elif part == "rpn":
            macs["rpn"] += conv_macs(th, tw, kh, kw, cin, cout)
        elif part == "head":
            oh, ow = hh // s, hw // s
            macs["head"] += conv_macs(oh, ow, kh, kw, cin, cout)
            if path.endswith("conv2"):
                hh, hw = oh, ow
        else:                                      # head_fc, per RoI
            macs["head"] += cin * cout
    macs["head"] *= net["test_post_nms"]
    out = {k: 2 * v for k, v in macs.items()}
    out["total"] = sum(out.values())
    return out


def nms_work(n: int, max_out: int) -> dict:
    """Greedy NMS over ``n`` score-sorted boxes keeping ``max_out``: each
    kept box is compared with every box once.  One IoU is 16 operations
    (4 min/max, 2 subtract+1 for each side and clamp, the products, the
    union and the compare).  Bytes: the boxes and scores read once, the
    kept indices and mask written once."""
    return {"ops": 16 * n * max_out,
            "bytes": n * 4 * 4 + n * 4 + max_out * (4 + 1)}


def roofline_seconds(ops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds the chip could take, which bound sets it)."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
