"""Plain reference: Faster R-CNN with a ResNet C4 trunk, inference forward.

Written from the published descriptions (He et al. 2016 bottleneck ResNet;
Ren et al. 2015 RPN + proposal layer; He et al. 2017 RoIAlign; the
py-faster-rcnn / mx-rcnn test-time post-process), in straightforward
``jax.numpy`` / numpy, float32, ``precision=HIGHEST``.  No kernels, no
batching, no cache.  It imports nothing of the program under test and takes
nothing the program made: weights come from ``benchmark.weights`` (the seed),
the image from the request body the generator sent.

``precision`` selects the arithmetic of every conv / matmul:
  "f32"  — float32, HIGHEST (the reference proper);
  "fp8"  — inputs and weights of every conv / matmul rounded to
           float8_e4m3 with a per-tensor scale, float32 accumulation: the
           nearest precision below the configuration's bfloat16, used only
           as the control of the ``correct`` comparison.

Departures from the sources, all stated in the configuration's ``assumed``:
stride on the 3x3 conv of a downsampling bottleneck (the "v1.5" placement);
frozen BN eps 2e-5; RoIAlign without the half-pixel shift (the original,
``aligned=False`` form) with one sample per bin; legacy "+1" box widths.
"""

from __future__ import annotations

import base64
import functools

import jax
import jax.numpy as jnp
import numpy as np

UNITS = {"resnet50": (3, 4, 6, 3), "resnet101": (3, 4, 23, 3)}
BN_EPS = 2e-5
HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------- the layers

def conv_layers(depth: str = "resnet101", num_classes: int = 81,
                num_anchors: int = 9):
    """Every conv / fc of the network as data: (path, kh, kw, cin, cout,
    stride, has_bn, has_bias, part).  ``part`` is "trunk" (once per image),
    "rpn" (once per image, on the stride-16 map) or "head" (once per RoI, on
    the 14x14 crop; stride applies inside).  ``benchmark.weights`` draws the
    weights from this list and ``benchmark.flops`` counts the work from it."""
    out = [("backbone/conv1", 7, 7, 3, 64, 2, "backbone/bn1", False, "trunk")]

    def stage(prefix, units, cin, filters, stride, part):
        for u in range(1, units + 1):
            p = f"{prefix}/unit{u}"
            s = stride if u == 1 else 1
            out.append((f"{p}/conv1", 1, 1, cin, filters, 1, f"{p}/bn1",
                        False, part))
            out.append((f"{p}/conv2", 3, 3, filters, filters, s, f"{p}/bn2",
                        False, part))
            out.append((f"{p}/conv3", 1, 1, filters, 4 * filters, 1,
                        f"{p}/bn3", False, part))
            if u == 1:
                out.append((f"{p}/sc_conv", 1, 1, cin, 4 * filters, s,
                            f"{p}/sc_bn", False, part))
            cin = 4 * filters
        return cin

    u = UNITS[depth]
    c = stage("backbone/stage1", u[0], 64, 64, 1, "trunk")
    c = stage("backbone/stage2", u[1], c, 128, 2, "trunk")
    c = stage("backbone/stage3", u[2], c, 256, 2, "trunk")
    out.append(("rpn/rpn_conv_3x3", 3, 3, c, 512, 1, None, True, "rpn"))
    out.append(("rpn/rpn_cls_score", 1, 1, 512, 2 * num_anchors, 1, None,
                True, "rpn"))
    out.append(("rpn/rpn_bbox_pred", 1, 1, 512, 4 * num_anchors, 1, None,
                True, "rpn"))
    c = stage("head_body/stage4", u[3], c, 512, 2, "head")
    out.append(("rcnn_out/cls_score", 0, 0, c, num_classes, 1, None, True,
                "head_fc"))
    out.append(("rcnn_out/bbox_pred", 0, 0, c, 4 * num_classes, 1, None, True,
                "head_fc"))
    return out


# ------------------------------------------------------------- arithmetic

def _fq8(x):
    """Round to float8_e4m3 under a per-tensor scale, back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _prep(x, w, precision):
    if precision == "fp8":
        return _fq8(x), _fq8(w)
    return x, w


def conv(x, w, stride, precision):
    x, w = _prep(x, w, precision)
    k = w.shape[0]
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(k // 2, k // 2)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def dense(x, w, b, precision):
    x, w = _prep(x, w, precision)
    return jnp.dot(x, w, precision=HIGHEST) + b


def bn(x, p, name):
    scale = p[f"{name}/gamma"] / jnp.sqrt(p[f"{name}/var"] + BN_EPS)
    return x * scale + (p[f"{name}/beta"] - p[f"{name}/mean"] * scale)


def bottleneck(x, p, prefix, stride, project, precision):
    def cbn(h, c, b, s):
        return bn(conv(h, p[f"{prefix}/{c}/kernel"], s, precision), p,
                  f"{prefix}/{b}")
    out = jax.nn.relu(cbn(x, "conv1", "bn1", 1))
    out = jax.nn.relu(cbn(out, "conv2", "bn2", stride))
    out = cbn(out, "conv3", "bn3", 1)
    sc = cbn(x, "sc_conv", "sc_bn", stride) if project else x
    return jax.nn.relu(out + sc)


def stage(x, p, prefix, units, stride, precision):
    x = bottleneck(x, p, f"{prefix}/unit1", stride, True, precision)
    for u in range(2, units + 1):
        x = bottleneck(x, p, f"{prefix}/unit{u}", 1, False, precision)
    return x


@functools.partial(jax.jit, static_argnames=("depth", "precision"))
def trunk_and_rpn(p, image, depth="resnet101", precision="f32"):
    """image (1, H, W, 3) normalised float32 -> (feat (1, H/16, W/16, 1024),
    rpn logits (N, 2), rpn deltas (N, 4)), N = H/16 * W/16 * A, anchor index
    (y * W + x) * A + a."""
    u = UNITS[depth]
    x = bn(conv(image, p["backbone/conv1/kernel"], 2, precision), p,
           "backbone/bn1")
    x = jax.nn.relu(x)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
    x = stage(x, p, "backbone/stage1", u[0], 1, precision)
    x = stage(x, p, "backbone/stage2", u[1], 2, precision)
    feat = stage(x, p, "backbone/stage3", u[2], 2, precision)
    h = jax.nn.relu(conv(feat, p["rpn/rpn_conv_3x3/kernel"], 1, precision)
                    + p["rpn/rpn_conv_3x3/bias"])
    cls = conv(h, p["rpn/rpn_cls_score/kernel"], 1, precision) \
        + p["rpn/rpn_cls_score/bias"]
    box = conv(h, p["rpn/rpn_bbox_pred/kernel"], 1, precision) \
        + p["rpn/rpn_bbox_pred/bias"]
    return feat, cls.reshape(-1, 2), box.reshape(-1, 4)


def roi_align(feat, rois, pooled, spatial_scale):
    """Original RoIAlign, one sample per bin: feat (H, W, C), rois (R, 4) in
    image coordinates -> (R, pooled, pooled, C).  Samples outside the map by
    a cell or more contribute 0; the rest clamp to the border."""
    h, w, _ = feat.shape
    x1 = rois[:, 0] * spatial_scale
    y1 = rois[:, 1] * spatial_scale
    bw = jnp.maximum(rois[:, 2] * spatial_scale - x1, 1.0) / pooled
    bh = jnp.maximum(rois[:, 3] * spatial_scale - y1, 1.0) / pooled
    c = jnp.arange(pooled, dtype=jnp.float32) + 0.5
    ys = y1[:, None] + c[None, :] * bh[:, None]           # (R, P)
    xs = x1[:, None] + c[None, :] * bw[:, None]

    def axis(t, n):
        ok = (t > -1.0) & (t < n)
        t = jnp.clip(t, 0.0, n - 1.0)
        t0 = jnp.floor(t)
        t1 = jnp.minimum(t0 + 1.0, n - 1.0)
        return ok, t0.astype(jnp.int32), t1.astype(jnp.int32), t - t0

    oky, y0, y1i, ly = axis(ys, h)
    okx, x0, x1i, lx = axis(xs, w)

    def g(yi, xi):
        return feat[yi[:, :, None], xi[:, None, :]]        # (R, P, P, C)

    ly = ly[:, :, None, None]
    lx = lx[:, None, :, None]
    out = ((1 - ly) * (1 - lx) * g(y0, x0) + (1 - ly) * lx * g(y0, x1i)
           + ly * (1 - lx) * g(y1i, x0) + ly * lx * g(y1i, x1i))
    ok = oky[:, :, None, None] & okx[:, None, :, None]
    return jnp.where(ok, out, 0.0)


@functools.partial(jax.jit, static_argnames=("depth", "precision"))
def head(p, feat, rois, depth="resnet101", precision="f32"):
    """feat (H/16, W/16, 1024), rois (R, 4) scaled-image coordinates ->
    (class probabilities (R, K), box deltas (R, 4K))."""
    crops = roi_align(feat, rois, 14, 1.0 / 16)
    x = stage(crops, p, "head_body/stage4", UNITS[depth][3], 2, precision)
    emb = jnp.mean(x, axis=(1, 2))
    cls = dense(emb, p["rcnn_out/cls_score/kernel"],
                p["rcnn_out/cls_score/bias"], precision)
    box = dense(emb, p["rcnn_out/bbox_pred/kernel"],
                p["rcnn_out/bbox_pred/bias"], precision)
    return jax.nn.softmax(cls, axis=-1), box


# ----------------------------------------------------- host side, in numpy

def decode_body(doc: dict) -> np.ndarray:
    """The request body as the generator made it -> (H, W, 3) uint8."""
    h, w, c = doc["shape"]
    return np.frombuffer(base64.b64decode(doc["data"]),
                         np.uint8).reshape(h, w, c)


def resize_bilinear(im: np.ndarray, s: float) -> np.ndarray:
    """Bilinear resize by the factor ``s`` on both axes, half-pixel centres,
    no antialiasing, border replicated: out[i] samples src at
    (i + 0.5) / s - 0.5.  Output extent round-half-even(dim * s)."""
    def axis(n):
        m = int(round(n * s))
        t = (np.arange(m, dtype=np.float64) + 0.5) / s - 0.5
        t0 = np.floor(t)
        f = (t - t0).astype(np.float32)
        i0 = np.clip(t0, 0, n - 1).astype(np.int64)
        i1 = np.clip(t0 + 1, 0, n - 1).astype(np.int64)
        return i0, i1, f

    y0, y1, fy = axis(im.shape[0])
    x0, x1, fx = axis(im.shape[1])
    rows = im[y0] * (1 - fy)[:, None, None] + im[y1] * fy[:, None, None]
    return rows[:, x0] * (1 - fx)[None, :, None] + rows[:, x1] * fx[None, :, None]


def prepare(im: np.ndarray, scale, means, stds, stride: int = 32):
    """uint8 image -> (bucket-padded normalised float32 (Hb, Wb, 3),
    (eff_h, eff_w, s)): normalise, resize so the short side reaches
    scale[0] unless the long side would pass scale[1], zero-pad to the
    orientation's bucket."""
    h, w = im.shape[:2]
    s = float(scale[0]) / min(h, w)
    if s * max(h, w) > scale[1]:
        s = float(scale[1]) / max(h, w)
    x = (im.astype(np.float32) - np.asarray(means, np.float32)) \
        / np.asarray(stds, np.float32)
    x = resize_bilinear(x, s)
    up = lambda v: int(np.ceil(v / stride) * stride)
    hb, wb = ((up(scale[0]), up(scale[1])) if w >= h
              else (up(scale[1]), up(scale[0])))
    x = x[:hb, :wb]
    out = np.zeros((hb, wb, 3), np.float32)
    out[:x.shape[0], :x.shape[1]] = x
    return out, (x.shape[0], x.shape[1], s)


def base_anchors(base=16, ratios=(0.5, 1.0, 2.0), scales=(8, 16, 32)):
    ctr = (base - 1) / 2.0
    out = []
    for r in ratios:
        ws = np.round(np.sqrt(base * base / r))
        hs = np.round(ws * r)
        for sc in scales:
            w, h = ws * sc, hs * sc
            out.append([ctr - (w - 1) / 2, ctr - (h - 1) / 2,
                        ctr + (w - 1) / 2, ctr + (h - 1) / 2])
    return np.asarray(out, np.float32)


def grid_anchors(fh, fw, stride=16):
    sx, sy = np.meshgrid(np.arange(fw) * stride, np.arange(fh) * stride)
    shifts = np.stack([sx.ravel(), sy.ravel(), sx.ravel(), sy.ravel()], 1)
    return (shifts[:, None, :] + base_anchors(stride)[None]).reshape(
        -1, 4).astype(np.float32)


def decode_boxes(boxes, deltas):
    """(N, 4) boxes, (N, 4K) deltas -> (N, 4K) boxes, "+1" widths."""
    w = boxes[:, 2:3] - boxes[:, 0:1] + 1.0
    h = boxes[:, 3:4] - boxes[:, 1:2] + 1.0
    cx = boxes[:, 0:1] + 0.5 * (w - 1.0)
    cy = boxes[:, 1:2] + 0.5 * (h - 1.0)
    pcx = deltas[:, 0::4] * w + cx
    pcy = deltas[:, 1::4] * h + cy
    pw = np.exp(deltas[:, 2::4]) * w
    ph = np.exp(deltas[:, 3::4]) * h
    out = np.empty_like(deltas)
    out[:, 0::4] = pcx - 0.5 * (pw - 1.0)
    out[:, 1::4] = pcy - 0.5 * (ph - 1.0)
    out[:, 2::4] = pcx + 0.5 * (pw - 1.0)
    out[:, 3::4] = pcy + 0.5 * (ph - 1.0)
    return out


def clip(boxes, h, w):
    out = boxes.copy()
    out[:, 0::2] = np.clip(boxes[:, 0::2], 0.0, w - 1.0)
    out[:, 1::2] = np.clip(boxes[:, 1::2], 0.0, h - 1.0)
    return out


def iou_one_many(box, boxes):
    iw = np.minimum(box[2], boxes[:, 2]) - np.maximum(box[0], boxes[:, 0]) + 1
    ih = np.minimum(box[3], boxes[:, 3]) - np.maximum(box[1], boxes[:, 1]) + 1
    inter = np.maximum(iw, 0) * np.maximum(ih, 0)
    a = (box[2] - box[0] + 1) * (box[3] - box[1] + 1)
    b = (boxes[:, 2] - boxes[:, 0] + 1) * (boxes[:, 3] - boxes[:, 1] + 1)
    return inter / (a + b - inter)


def greedy_nms(boxes, scores, thresh, max_out=None):
    """Indices kept by greedy NMS, score-descending; suppress IoU > thresh."""
    order = np.argsort(-scores, kind="stable")
    keep = []
    alive = np.ones(len(order), bool)
    for pos, i in enumerate(order):
        if not alive[pos]:
            continue
        keep.append(i)
        if max_out is not None and len(keep) >= max_out:
            break
        rest = order[pos + 1:]
        alive[pos + 1:] &= ~(iou_one_many(boxes[i], boxes[rest]) > thresh)
    return np.asarray(keep, np.int64)


def proposals(logits, deltas, anchors, eh, ew, s, pre=6000, post=300,
              thresh=0.7, min_size=16):
    """The proposal layer -> (post, 4) boxes (fewer if NMS leaves fewer)."""
    scores = 1.0 / (1.0 + np.exp(-(logits[:, 1] - logits[:, 0])))
    boxes = clip(decode_boxes(anchors, deltas), eh, ew)
    ok = ((boxes[:, 2] - boxes[:, 0] + 1 >= min_size * s)
          & (boxes[:, 3] - boxes[:, 1] + 1 >= min_size * s))
    idx = np.flatnonzero(ok)
    idx = idx[np.argsort(-scores[idx], kind="stable")[:pre]]
    keep = greedy_nms(boxes[idx], scores[idx], thresh, post)
    return boxes[idx][keep]


def records(prob, boxes, num_classes, thresh=1e-3, nms=0.3, cap=100):
    """Per-class threshold -> NMS -> global cap, as a score-sorted list."""
    out = []
    for k in range(1, num_classes):
        sel = prob[:, k] > thresh
        b, sc = boxes[sel, 4 * k:4 * k + 4], prob[sel, k]
        for i in greedy_nms(b, sc, nms):
            out.append({"cls": k, "score": float(sc[i]),
                        "bbox": [float(c) for c in b[i]]})
    out.sort(key=lambda r: -r["score"])
    return out[:cap] if cap > 0 else out


def detect(p, doc: dict, net: dict, precision: str = "f32",
           roi_block: int = 100):
    """One request body -> the dense candidates the reference stands by:
    (prob (R, K), boxes (R, 4K) in the original image's coordinates).
    ``net`` holds the configuration's numbers (see configs/<name>.json)."""
    im = decode_body(doc)
    x, (eh, ew, s) = prepare(im, net["scale"], net["pixel_means"],
                             net["pixel_stds"], net["image_stride"])
    feat, logits, deltas = trunk_and_rpn(p, jnp.asarray(x[None]),
                                         depth=net["depth"],
                                         precision=precision)
    logits, deltas = np.asarray(logits), np.asarray(deltas)
    anchors = grid_anchors(feat.shape[1], feat.shape[2])
    rois = proposals(logits, deltas, anchors, eh, ew, s,
                     net["test_pre_nms"], net["test_post_nms"],
                     net["rpn_nms_thresh"], net["rpn_min_size"])
    n = len(rois)
    pad = (-n) % roi_block           # fixed block shape: one compile
    rois_p = np.concatenate([rois, np.repeat(rois[:1], pad, 0)])
    probs, dls = [], []
    for i in range(0, len(rois_p), roi_block):
        pr, dl = head(p, feat[0], jnp.asarray(rois_p[i:i + roi_block]),
                      depth=net["depth"], precision=precision)
        probs.append(np.asarray(pr))
        dls.append(np.asarray(dl))
    prob = np.concatenate(probs)[:n]
    dl = np.concatenate(dls)[:n]
    boxes = clip(decode_boxes(rois, dl), eh, ew) / s
    return prob, boxes
