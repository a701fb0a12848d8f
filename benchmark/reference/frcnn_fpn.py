"""Plain reference: Faster R-CNN on a ResNet Feature Pyramid Network,
inference forward.  Fills the ``reference`` row of ``benchmark/README.md``,
"A configuration", for ``r101-fpn``.

Written from the published descriptions — Lin et al. 2017, "Feature Pyramid
Networks for Object Detection" (arXiv:1612.03144): section 3 (lateral 1x1,
top-down 2x, 3x3 smoothing), section 4.1 (one RPN head shared by the levels,
one anchor scale a level, P6 for the RPN only), section 4.2 (eq. 1 level
map, the 2-FC head on 7x7 crops) — and from the test settings of its public
baseline (Detectron ``e2e_faster_rcnn_R-101-FPN_1x.yaml``: 1000 proposals a
level before NMS, 1000 after), in straightforward ``jax.numpy`` / numpy,
float32, ``precision=HIGHEST``.  No kernels, no batching, no cache.  It
imports nothing of the program under test and takes nothing the program
made; what is general to both detectors (a conv, frozen BN, a bottleneck
stage, the request body's decode and resize, box arithmetic, greedy NMS,
the record list) comes from ``benchmark.reference.frcnn_c4``.

The trunk runs the plain 7x7 stride-2 stem on the 3-channel image (the
program regroups the image 2x2 space-to-depth on the host and runs a 4x4
stem: the same arithmetic, reached another way).

``precision``: "f32" (the reference proper) or "fp8" (every conv / matmul
input and weight rounded to float8_e4m3 under a per-tensor scale, float32
accumulation: the control of the ``correct`` comparison).

Departures from the sources, all stated in the configuration's ``assumed``
and followed here because the program makes them: ONE joint NMS over the
levels' concatenated top-k (Detectron runs NMS a level, then keeps the 1000
best); legacy "+1" box widths in the level map and the decode; nearest
top-down upsampling; P6 = P5 subsampled by 2 (no pooling window), used by
the RPN only; frozen BN eps 2e-5 with the stride on the 3x3 conv; RoIAlign
without the half-pixel shift, 2x2 samples a bin, a sample a whole cell
outside the map contributing 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.frcnn_c4 import (UNITS, base_anchors, bn, clip,
                                          conv, decode_body, decode_boxes,
                                          dense, greedy_nms, prepare, stage)

LEVELS = (2, 3, 4, 5, 6)            # P2..P6; P6 feeds the RPN only
POOLED = 7
SAMPLES = 2


# --------------------------------------------------------------- the layers

def conv_layers(depth: str = "resnet101", num_classes: int = 81,
                num_anchors: int = 3, channels: int = 256,
                hidden: int = 1024):
    """Every conv / fc of the network as data: (path, kh, kw, cin, cout,
    stride, bn, has_bias, part).  ``part``: "trunk" (once an image, C2..C5),
    "neck" (once an image, on the level the path's digit names), "rpn"
    (once an image on EACH of the five levels), "head_fc" (once a RoI;
    kh = 0 marks a matmul).  The configuration's ``weights`` module draws
    from this list and its ``flops`` module counts from it."""
    out = [("backbone/conv1", 7, 7, 3, 64, 2, "backbone/bn1", False, "trunk")]
    cin = 64
    for s, (units, filters) in enumerate(zip(UNITS[depth],
                                             (64, 128, 256, 512)), start=1):
        for u in range(1, units + 1):
            p = f"backbone/stage{s}/unit{u}"
            st = 2 if (u == 1 and s > 1) else 1
            out.append((f"{p}/conv1", 1, 1, cin, filters, 1, f"{p}/bn1",
                        False, "trunk"))
            out.append((f"{p}/conv2", 3, 3, filters, filters, st, f"{p}/bn2",
                        False, "trunk"))
            out.append((f"{p}/conv3", 1, 1, filters, 4 * filters, 1,
                        f"{p}/bn3", False, "trunk"))
            if u == 1:
                out.append((f"{p}/sc_conv", 1, 1, cin, 4 * filters, st,
                            f"{p}/sc_bn", False, "trunk"))
            cin = 4 * filters
    for lvl, c in zip((2, 3, 4, 5), (256, 512, 1024, 2048)):
        out.append((f"neck/lateral{lvl}", 1, 1, c, channels, 1, None, True,
                    "neck"))
    for lvl in (2, 3, 4, 5):
        out.append((f"neck/post{lvl}", 3, 3, channels, channels, 1, None,
                    True, "neck"))
    out.append(("rpn/rpn_conv_3x3", 3, 3, channels, channels, 1, None, True,
                "rpn"))
    out.append(("rpn/rpn_cls_score", 1, 1, channels, 2 * num_anchors, 1, None,
                True, "rpn"))
    out.append(("rpn/rpn_bbox_pred", 1, 1, channels, 4 * num_anchors, 1, None,
                True, "rpn"))
    out.append(("head_body/fc6", 0, 0, POOLED * POOLED * channels, hidden, 1,
                None, True, "head_fc"))
    out.append(("head_body/fc7", 0, 0, hidden, hidden, 1, None, True,
                "head_fc"))
    out.append(("rcnn_out/cls_score", 0, 0, hidden, num_classes, 1, None,
                True, "head_fc"))
    out.append(("rcnn_out/bbox_pred", 0, 0, hidden, 4 * num_classes, 1, None,
                True, "head_fc"))
    return out


# ------------------------------------------------------------- on the device

def _biased(x, p, name, stride, precision):
    return conv(x, p[f"{name}/kernel"], stride, precision) + p[f"{name}/bias"]


def _up2(x):
    """Nearest-neighbour 2x: every cell repeated along both axes."""
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def trunk(p, image, depth, precision):
    """image (1, H, W, 3) -> C2..C5 at strides 4, 8, 16, 32."""
    u = UNITS[depth]
    x = jax.nn.relu(bn(conv(image, p["backbone/conv1/kernel"], 2, precision),
                       p, "backbone/bn1"))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
    c2 = stage(x, p, "backbone/stage1", u[0], 1, precision)
    c3 = stage(c2, p, "backbone/stage2", u[1], 2, precision)
    c4 = stage(c3, p, "backbone/stage3", u[2], 2, precision)
    c5 = stage(c4, p, "backbone/stage4", u[3], 2, precision)
    return c2, c3, c4, c5


def neck(p, c2, c3, c4, c5, precision):
    """C2..C5 -> P2..P6 (Lin et al. section 3; P6 as section 4.1's
    subsampling of P5)."""
    p5 = _biased(c5, p, "neck/lateral5", 1, precision)
    p4 = _biased(c4, p, "neck/lateral4", 1, precision) + _up2(p5)
    p3 = _biased(c3, p, "neck/lateral3", 1, precision) + _up2(p4)
    p2 = _biased(c2, p, "neck/lateral2", 1, precision) + _up2(p3)
    p2, p3, p4, p5 = (_biased(x, p, f"neck/post{i}", 1, precision)
                      for i, x in ((2, p2), (3, p3), (4, p4), (5, p5)))
    return p2, p3, p4, p5, p5[:, ::2, ::2]


def rpn(p, feat, precision):
    """One level's map (1, h, w, C) -> (logits (h*w*A, 2), deltas (h*w*A,
    4)), anchor index (y * w + x) * A + a."""
    h = jax.nn.relu(_biased(feat, p, "rpn/rpn_conv_3x3", 1, precision))
    return (_biased(h, p, "rpn/rpn_cls_score", 1, precision).reshape(-1, 2),
            _biased(h, p, "rpn/rpn_bbox_pred", 1, precision).reshape(-1, 4))


@functools.partial(jax.jit, static_argnames=("depth", "precision"))
def pyramid_and_rpn(p, image, depth="resnet101", precision="f32"):
    """image (1, H, W, 3) normalised float32 -> (P2..P6, [(logits, deltas)
    a level])."""
    feats = neck(p, *trunk(p, image, depth, precision), precision)
    return feats, [rpn(p, f, precision) for f in feats]


def roi_align(feat, rois, pooled, spatial_scale, samples):
    """RoIAlign (He et al. 2017) in its original ``aligned=False`` form:
    feat (H, W, C), rois (R, 4) image coordinates -> (R, pooled, pooled, C),
    each bin the mean of ``samples`` x ``samples`` bilinear samples at
    (i + 0.5) / samples of the bin.  A sample a cell or more outside the map
    contributes 0; the rest clamp to the border."""
    h, w, _ = feat.shape
    x1 = rois[:, 0] * spatial_scale
    y1 = rois[:, 1] * spatial_scale
    bw = jnp.maximum(rois[:, 2] * spatial_scale - x1, 1.0) / pooled
    bh = jnp.maximum(rois[:, 3] * spatial_scale - y1, 1.0) / pooled
    off = (jnp.arange(pooled, dtype=jnp.float32)[:, None]
           + (jnp.arange(samples, dtype=jnp.float32)[None, :] + 0.5)
           / samples).reshape(-1)                          # (P * S,)
    ys = y1[:, None] + off[None, :] * bh[:, None]          # (R, P * S)
    xs = x1[:, None] + off[None, :] * bw[:, None]

    def axis(t, n):
        ok = (t > -1.0) & (t < n)
        t = jnp.clip(t, 0.0, n - 1.0)
        t0 = jnp.floor(t)
        t1 = jnp.minimum(t0 + 1.0, n - 1.0)
        return ok, t0.astype(jnp.int32), t1.astype(jnp.int32), t - t0

    oky, y0, y1i, ly = axis(ys, h)
    okx, x0, x1i, lx = axis(xs, w)

    def g(yi, xi):
        return feat[yi[:, :, None], xi[:, None, :]]        # (R, PS, PS, C)

    ly = ly[:, :, None, None]
    lx = lx[:, None, :, None]
    out = ((1 - ly) * (1 - lx) * g(y0, x0) + (1 - ly) * lx * g(y0, x1i)
           + ly * (1 - lx) * g(y1i, x0) + ly * lx * g(y1i, x1i))
    out = jnp.where(oky[:, :, None, None] & okx[:, None, :, None], out, 0.0)
    r, c = rois.shape[0], feat.shape[-1]
    return out.reshape(r, pooled, samples, pooled, samples, c).mean((2, 4))


@functools.partial(jax.jit, static_argnames=("stride",))
def pool(feat, rois, stride):
    """One level's map (h, w, C), rois (R, 4) -> (R, 7, 7, C)."""
    return roi_align(feat, rois, POOLED, 1.0 / stride, SAMPLES)


@functools.partial(jax.jit, static_argnames=("precision",))
def box_head(p, crops, precision="f32"):
    """crops (R, 7, 7, C) -> (class probabilities (R, K), deltas (R, 4K)):
    Lin et al. section 4.2's two hidden 1024-d fc layers, then the two
    sibling outputs."""
    x = crops.reshape(crops.shape[0], -1)
    for name in ("head_body/fc6", "head_body/fc7"):
        x = jax.nn.relu(dense(x, p[f"{name}/kernel"], p[f"{name}/bias"],
                              precision))
    cls = dense(x, p["rcnn_out/cls_score/kernel"],
                p["rcnn_out/cls_score/bias"], precision)
    box = dense(x, p["rcnn_out/bbox_pred/kernel"],
                p["rcnn_out/bbox_pred/bias"], precision)
    return jax.nn.softmax(cls, axis=-1), box


# ----------------------------------------------------- host side, in numpy

def level_anchors(fh: int, fw: int, stride: int, scale: int,
                  ratios=(0.5, 1.0, 2.0)) -> np.ndarray:
    """One anchor scale a level (section 4.1): areas (scale * stride)**2 =
    32**2 .. 512**2 on P2..P6 at three aspect ratios."""
    sx, sy = np.meshgrid(np.arange(fw) * stride, np.arange(fh) * stride)
    shifts = np.stack([sx.ravel(), sy.ravel(), sx.ravel(), sy.ravel()], 1)
    base = base_anchors(stride, ratios, (scale,))
    return (shifts[:, None, :] + base[None]).reshape(-1, 4).astype(np.float32)


def level_candidates(logits, deltas, anchors, eh, ew, s, k, min_size):
    """One level's best ``k`` (fewer where the level has fewer): decoded,
    clipped boxes no smaller than ``min_size`` x the resize factor, by
    objectness -> (boxes, scores)."""
    scores = 1.0 / (1.0 + np.exp(-(logits[:, 1] - logits[:, 0])))
    boxes = clip(decode_boxes(anchors, deltas), eh, ew)
    ok = ((boxes[:, 2] - boxes[:, 0] + 1 >= min_size * s)
          & (boxes[:, 3] - boxes[:, 1] + 1 >= min_size * s))
    idx = np.flatnonzero(ok)
    idx = idx[np.argsort(-scores[idx], kind="stable")[:k]]
    return boxes[idx], scores[idx]


def proposals(per_level, eh, ew, s, net: dict):
    """The proposal layer over the pyramid: each level's top
    ``test_pre_nms_per_level``, concatenated, then ONE greedy NMS
    (``assumed``) -> (boxes (<= test_post_nms, 4), scores)."""
    cand = [level_candidates(lg, dl, an, eh, ew, s,
                             net["test_pre_nms_per_level"],
                             net["rpn_min_size"])
            for lg, dl, an in per_level]
    boxes = np.concatenate([b for b, _ in cand])
    scores = np.concatenate([sc for _, sc in cand])
    keep = greedy_nms(boxes, scores, net["rpn_nms_thresh"],
                      net["test_post_nms"])
    return boxes[keep], scores[keep]


def assign_level(rois: np.ndarray, k0: int = 4, canon: float = 224.0):
    """Lin et al. eq. 1, k = floor(k0 + log2(sqrt(w h) / 224)), held to
    P2..P5; "+1" widths (``assumed``)."""
    w = rois[:, 2] - rois[:, 0] + 1.0
    h = rois[:, 3] - rois[:, 1] + 1.0
    k = np.floor(k0 + np.log2(np.sqrt(w * h) / canon + 1e-8))
    return np.clip(k, 2, 5).astype(np.int64)


def pool_on_levels(feats, rois, levels, strides, block: int = 100):
    """Every RoI pooled once, on its own level's map -> (R, 7, 7, C)."""
    out = np.zeros((len(rois), POOLED, POOLED, feats[0].shape[-1]),
                   np.float32)
    for li, lvl in enumerate(LEVELS[:4]):
        idx = np.flatnonzero(levels == lvl)
        for i in range(0, len(idx), block):        # fixed block: one compile
            sel = idx[i:i + block]
            pad = np.concatenate([sel, np.repeat(sel[:1], block - len(sel))])
            out[sel] = np.asarray(pool(feats[li][0], jnp.asarray(rois[pad]),
                                       stride=strides[li]))[:len(sel)]
    return out


def detect(p, doc: dict, net: dict, precision: str = "f32",
           roi_block: int = 100, stages: dict | None = None):
    """One request body -> the dense candidates the reference stands by:
    (prob (R, K), boxes (R, 4K) in the original image's coordinates).
    ``net`` holds the configuration's numbers (configs/r101-fpn.json);
    ``stages``, where given, receives every intermediate by name (the CPU
    tests compare the program with them one by one)."""
    im = decode_body(doc)
    x, (eh, ew, s) = prepare(im, net["scale"], net["pixel_means"],
                             net["pixel_stds"], net["image_stride"])
    feats, heads = pyramid_and_rpn(p, jnp.asarray(x[None]),
                                   depth=net["depth"], precision=precision)
    strides = net["strides"]
    per_level = [(np.asarray(lg), np.asarray(dl),
                  level_anchors(f.shape[1], f.shape[2], st, net["anchor_scale"],
                                net["anchor_ratios"]))
                 for f, (lg, dl), st in zip(feats, heads, strides)]
    rois, roi_scores = proposals(per_level, eh, ew, s, net)
    levels = assign_level(rois)
    crops = pool_on_levels(feats, rois, levels, strides, roi_block)
    n = len(rois)
    pad = (-n) % roi_block
    crops_p = np.concatenate([crops, np.repeat(crops[:1], pad, 0)])
    probs, dls = [], []
    for i in range(0, len(crops_p), roi_block):
        pr, dl = box_head(p, jnp.asarray(crops_p[i:i + roi_block]),
                          precision=precision)
        probs.append(np.asarray(pr))
        dls.append(np.asarray(dl))
    prob = np.concatenate(probs)[:n]
    dl = np.concatenate(dls)[:n]
    if stages is not None:
        stages.update(image=x, im_info=(eh, ew, s), feats=feats,
                      per_level=per_level, rois=rois, roi_scores=roi_scores,
                      levels=levels, crops=crops, prob=prob, deltas=dl)
    boxes = clip(decode_boxes(rois, dl), eh, ew) / s
    return prob, boxes
