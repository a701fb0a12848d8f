"""Plain reference: Mask R-CNN on a ResNet Feature Pyramid Network, inference
forward.  Fills the ``reference`` row of ``benchmark/README.md``, "A
configuration", for ``r101-fpn-mask``.

Written from the published description — He, Gkioxari, Dollar, Girshick
2017, "Mask R-CNN" (arXiv:1703.06870): section 3 (the mask branch beside the
box branch, a per-class sigmoid map, RoIAlign) and figure 4 right (the FPN
head: RoIAlign 14x14, four 3x3 convs of 256, a 2x2 stride-2 deconv of 256,
a 1x1 conv to one 28x28 map a class); at test time the branch runs on the
**final detections** and the map of the predicted class is resized to the
box and cut at 0.5 (section 3.1, "Inference") — in straightforward
``jax.numpy`` / numpy, float32, ``precision=HIGHEST``.  No kernels, no
batching, no cache.  It imports nothing of the program under test; the box
path is ``benchmark.reference.frcnn_fpn``'s, the general arithmetic
``benchmark.reference.frcnn_c4``'s.

``detect`` takes nothing the program made: from the request body it gives
the dense box candidates (as ``frcnn_fpn.detect``) and keeps the request's
own float32 pyramid P2..P5.  ``masks`` is the branch as a function of a box
and a class: the comparison calls it with the served records' own boxes, so
every served mask is held to the reference exactly where it was computed,
while ``box_gap`` goes on judging the boxes.

``precision``: "f32" (the reference proper) or "fp8" (every conv / matmul
input and weight rounded to float8_e4m3 under a per-tensor scale: the
control of the ``correct`` comparison).

Departures from the paper and from Detectron, all stated in the
configuration's ``assumed`` and followed here because the program makes
them: the paste (integer window [floor x1, ceil x2] x [floor y1, ceil y2],
pixel j of an extent of n reads the M-bin map at (j + 0.5) M / n - 0.5
between its two neighbours with replicated borders, cut at >= 0.5;
Detectron pads the map by a pixel and grows the box by 30/28 first); 81
mask channels with the background's unused; RoIAlign without the half-pixel
shift, on the one level eq. 1 of the FPN paper assigns (k0 = 4, 224, legacy
+1 widths); the deconv's kernel stored as ``lax.conv_transpose`` reads it
(tap (a, b) of the 2x2 kernel writes output pixel (2i + 1 - a, 2j + 1 - b)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import frcnn_fpn
from benchmark.reference.frcnn_c4 import conv, decode_body, dense

MASK_CONVS = 4
ROI_BLOCK = 100          # RoIs a jitted call: one compile a level


# --------------------------------------------------------------- the layers

def mask_layers(num_classes: int = 81, channels: int = 256,
                convs: int = MASK_CONVS):
    """The mask head's layers as data, once a RoI: (path, kind, kh, kw,
    cin, cout, output side).  ``kind``: "conv" (3x3 on the 14x14 crop),
    "deconv" (2x2 stride 2, 14 -> 28) or "out" (1x1 on 28x28).  The
    configuration's ``weights`` module draws from this list and its
    ``flops`` module counts from it."""
    out = [(f"mask_head/mask_conv{i}", "conv", 3, 3, channels, channels, 14)
           for i in range(1, convs + 1)]
    out.append(("mask_head/mask_deconv", "deconv", 2, 2, channels, channels,
                28))
    out.append(("mask_head/mask_out", "out", 1, 1, channels, num_classes, 28))
    return out


# ------------------------------------------------------------- on the device

@functools.partial(jax.jit, static_argnames=("stride", "pooled", "samples"))
def pool(feat, rois, stride, pooled, samples):
    """One level's map (h, w, C), rois (R, 4) -> (R, pooled, pooled, C)."""
    return frcnn_fpn.roi_align(feat, rois, pooled, 1.0 / stride, samples)


@functools.partial(jax.jit, static_argnames=("convs", "precision"))
def mask_head(p, crops, convs=MASK_CONVS, precision="f32"):
    """crops (R, 14, 14, C) -> per-class sigmoid maps (R, 28, 28, K):
    figure 4 right."""
    x = crops
    for i in range(1, convs + 1):
        name = f"mask_head/mask_conv{i}"
        x = jax.nn.relu(conv(x, p[f"{name}/kernel"], 1, precision)
                        + p[f"{name}/bias"])
    # 2x2 stride-2 deconv: every input cell writes its own 2x2 output block
    k = p["mask_head/mask_deconv/kernel"][::-1, ::-1]   # (a, b, cin, cout)
    r, h, w, c = x.shape
    o = k.shape[-1]
    y = dense(x.reshape(-1, c), k.transpose(2, 0, 1, 3).reshape(c, 4 * o),
              0.0, precision)
    y = y.reshape(r, h, w, 2, 2, o).transpose(0, 1, 3, 2, 4, 5).reshape(
        r, 2 * h, 2 * w, o)
    x = jax.nn.relu(y + p["mask_head/mask_deconv/bias"])
    logits = conv(x, p["mask_head/mask_out/kernel"], 1, precision) \
        + p["mask_head/mask_out/bias"]
    return jax.nn.sigmoid(logits)


# ----------------------------------------------------- host side, in numpy

def paste_weights(lo: float, hi: float, frame: int, bins: int):
    """One axis of the paste: the box edge pair (lo, hi) on a frame of
    ``frame`` pixels -> (first visible pixel, (visible pixels, bins)
    interpolation matrix).  The window is [floor lo, ceil hi]; pixel j of
    its n = ceil hi - floor lo + 1 reads the map at (j + 0.5) bins / n - 0.5
    between its two neighbouring bins, borders replicated."""
    a, b = int(np.floor(lo)), int(np.ceil(hi))
    n = max(b - a + 1, 1)
    g0, g1 = max(a, 0), min(b, frame - 1)
    if g1 < g0:
        return 0, np.zeros((0, bins))
    j = np.arange(g0 - a, g1 - a + 1, dtype=np.float64)
    src = (j + 0.5) * bins / n - 0.5
    i0 = np.floor(src)
    f = src - i0
    wts = np.zeros((len(j), bins))
    rows = np.arange(len(j))
    np.add.at(wts, (rows, np.clip(i0, 0, bins - 1).astype(int)), 1.0 - f)
    np.add.at(wts, (rows, np.clip(i0 + 1, 0, bins - 1).astype(int)), f)
    return g0, wts


def paste(prob: np.ndarray, box, h: int, w: int):
    """One (M, M) probability map resized into its box's window of the
    (h, w) frame -> ((x0, y0), probabilities (rows, columns) of the visible
    window).  Not cut: the comparison cuts at 0.5 and reads the margin."""
    m = prob.shape[0]
    x0, wx = paste_weights(float(box[0]), float(box[2]), w, m)
    y0, wy = paste_weights(float(box[1]), float(box[3]), h, m)
    return (x0, y0), wy @ prob.astype(np.float64) @ wx.T


def detect(p, doc: dict, net: dict, precision: str = "f32",
           roi_block: int = 100, stages: dict | None = None) -> dict:
    """One request body -> what the reference stands by: the dense box
    candidates ``prob`` (R, K) and ``boxes`` (R, 4K) in the original image's
    coordinates, as ``frcnn_fpn.detect`` gives them, and for the mask branch
    the request's own pyramid ``feats`` (P2..P5, float32, on the device),
    its resize factor ``scale``, its raw ``hw``, and the mask head's
    parameters."""
    st = {} if stages is None else stages
    prob, boxes = frcnn_fpn.detect(p, doc, net, precision, roi_block, st)
    h, w = decode_body(doc).shape[:2]
    return {"prob": prob, "boxes": boxes,
            "feats": [f[0] for f in st["feats"][:4]],
            "scale": st["im_info"][2], "hw": (int(h), int(w)),
            "params": {k: v for k, v in p.items()
                       if k.startswith("mask_head/")},
            "precision": precision}


def mask_probs(dense_doc: dict, boxes: np.ndarray, labels, net: dict):
    """The branch up to its 28x28 maps: original-frame boxes (n, 4) and
    classes (n,) -> (n, M, M) probabilities of each box's own class."""
    boxes = np.asarray(boxes, np.float32).reshape(-1, 4)
    labels = np.asarray(labels, np.int64).reshape(-1)
    m = net["mask_size"]
    out = np.zeros((len(boxes), m, m), np.float32)
    if not len(boxes):
        return out
    scaled = boxes * np.float32(dense_doc["scale"])
    levels = frcnn_fpn.assign_level(scaled)
    for li, lvl in enumerate(frcnn_fpn.LEVELS[:4]):
        idx = np.flatnonzero(levels == lvl)
        for i in range(0, len(idx), ROI_BLOCK):
            sel = idx[i:i + ROI_BLOCK]
            pad = np.concatenate([sel, np.repeat(sel[:1],
                                                 ROI_BLOCK - len(sel))])
            crops = pool(dense_doc["feats"][li], jnp.asarray(scaled[pad]),
                         stride=net["strides"][li],
                         pooled=net["mask_pooled"],
                         samples=net["mask_samples"])
            maps = np.asarray(mask_head(dense_doc["params"], crops,
                                        convs=net["mask_convs"],
                                        precision=dense_doc["precision"]))
            out[sel] = maps[np.arange(len(sel)), :, :, labels[sel]]
    return out


def masks(dense_doc: dict, boxes: np.ndarray, labels, net: dict) -> list:
    """For original-frame boxes and classes, the pasted **probability** map
    of each in the request's frame: [((x0, y0), (rows, columns) float64)],
    the visible part of each box's paste window."""
    h, w = dense_doc["hw"]
    probs = mask_probs(dense_doc, boxes, labels, net)
    return [paste(pr, box, h, w)
            for pr, box in zip(probs, np.asarray(boxes).reshape(-1, 4))]
