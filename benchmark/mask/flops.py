"""``r101-fpn-mask``'s operations, counted from shapes alone: the ``flops``
row of ``benchmark/README.md``, "A configuration".  The predict program is
``r101-fpn``'s (``benchmark.fpn.flops``, as it is); the mask program's work
is counted from the plain reference's list of layers
(``reference.mrcnn_fpn.mask_layers``), never from the program under test or
the compiler's cost analysis.

A multiply-accumulate is two operations.  Counted, a RoI: the four 3x3
convs on the 14x14 crop, the 2x2 stride-2 deconv (each of the 196 input
cells writes four outputs), the 1x1 conv to every class on 28x28.  **Not
counted**, whatever implements it: RoIAlign's interpolation (a program that
pools every RoI on all four levels' whole maps spends MXU time the
architecture does not require: it is not credited), ReLU, the sigmoid, the
class selection, the paste.
"""

from __future__ import annotations

from benchmark.fpn.flops import (conv_macs, level_sizes, nms_work,  # noqa: F401
                                 predict_flops_per_image, roofline_seconds)
from benchmark.reference.mrcnn_fpn import mask_layers


def mask_macs_per_roi(net: dict) -> int:
    total = 0
    for (_path, kind, kh, kw, cin, cout, side) in mask_layers(
            net["num_classes"], net["mask_channels"], net["mask_convs"]):
        if kind == "deconv":        # a cell of the input a tap: side / 2
            total += (side // 2) ** 2 * kh * kw * cin * cout
        else:
            total += conv_macs(side, side, kh, kw, cin, cout)
    return total


def mask_flops_per_image(net: dict, rois: float) -> float:
    """FLOPs the mask head requires for ``rois`` RoIs of one image."""
    return 2.0 * mask_macs_per_roi(net) * rois
