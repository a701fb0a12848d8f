"""Box codecs and IoU.

Behavioral contracts of the reference's ``rcnn/processing/bbox_transform.py``
(``bbox_transform`` = encode, ``bbox_pred`` = decode, ``clip_boxes``) and
``rcnn/cython/bbox.pyx`` (``bbox_overlaps_cython``), rebuilt as jittable
jax.numpy functions.  The legacy "+1" width convention (w = x2 - x1 + 1) is
preserved throughout for numeric parity.

All functions are shape-polymorphic over leading dims and safe under jit.

This is the IN-GRAPH version: everything traced calls it (the proposal
layer, target assignment in training, ``ops.postprocess.device_postprocess``),
often with numpy constants beside tracers, so it stays ``jnp`` throughout
and does not look at what it is handed.  Host code that holds numpy arrays
a readback has already brought back must not call it — each op would run as
its own program on the device, with a transfer each way.  The host twin of
``bbox_pred`` + ``clip_boxes`` is ``ops.postprocess.decode_image_boxes``
(numpy only); ``tests/test_postprocess.py`` ties the two.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

# matches the reference's epsilon guard in nonlinear_transform
_EPS = 1e-14


def bbox_transform(ex_rois: jnp.ndarray, gt_rois: jnp.ndarray) -> jnp.ndarray:
    """Encode gt boxes w.r.t. example (anchor/RoI) boxes → (…, 4) deltas.

    delta = (dx, dy, dw, dh) with dx,dy normalized by ex width/height and
    dw,dh log-ratios (reference: nonlinear_transform).
    """
    ex_w = ex_rois[..., 2] - ex_rois[..., 0] + 1.0
    ex_h = ex_rois[..., 3] - ex_rois[..., 1] + 1.0
    ex_cx = ex_rois[..., 0] + 0.5 * (ex_w - 1.0)
    ex_cy = ex_rois[..., 1] + 0.5 * (ex_h - 1.0)

    gt_w = gt_rois[..., 2] - gt_rois[..., 0] + 1.0
    gt_h = gt_rois[..., 3] - gt_rois[..., 1] + 1.0
    gt_cx = gt_rois[..., 0] + 0.5 * (gt_w - 1.0)
    gt_cy = gt_rois[..., 1] + 0.5 * (gt_h - 1.0)

    dx = (gt_cx - ex_cx) / (ex_w + _EPS)
    dy = (gt_cy - ex_cy) / (ex_h + _EPS)
    dw = jnp.log(gt_w / (ex_w + _EPS))
    dh = jnp.log(gt_h / (ex_h + _EPS))
    return jnp.stack([dx, dy, dw, dh], axis=-1)


def bbox_pred(boxes: jnp.ndarray, deltas: jnp.ndarray) -> jnp.ndarray:
    """Decode deltas w.r.t. boxes (reference: nonlinear_pred / bbox_pred).

    boxes: (..., N, 4); deltas: (..., N, 4*K) class-specific layout → output
    (..., N, 4*K).  Works for K=1 (RPN) and K=num_classes (RCNN head).
    """
    w = boxes[..., 2:3] - boxes[..., 0:1] + 1.0
    h = boxes[..., 3:4] - boxes[..., 1:2] + 1.0
    cx = boxes[..., 0:1] + 0.5 * (w - 1.0)
    cy = boxes[..., 1:2] + 0.5 * (h - 1.0)

    dx = deltas[..., 0::4]
    dy = deltas[..., 1::4]
    dw = deltas[..., 2::4]
    dh = deltas[..., 3::4]

    pred_cx = dx * w + cx
    pred_cy = dy * h + cy
    pred_w = jnp.exp(dw) * w
    pred_h = jnp.exp(dh) * h

    x1 = pred_cx - 0.5 * (pred_w - 1.0)
    y1 = pred_cy - 0.5 * (pred_h - 1.0)
    x2 = pred_cx + 0.5 * (pred_w - 1.0)
    y2 = pred_cy + 0.5 * (pred_h - 1.0)

    # interleave back to (..., N, 4K): stack on a new trailing axis then fold
    out = jnp.stack([x1, y1, x2, y2], axis=-1)  # (..., N, K, 4)
    return out.reshape(*deltas.shape[:-1], deltas.shape[-1])


def clip_boxes(boxes: jnp.ndarray, im_h, im_w) -> jnp.ndarray:
    """Clip (..., 4K) boxes to [0, W-1] × [0, H-1] (reference: clip_boxes).

    im_h/im_w may be traced scalars (per-image effective size before padding).
    """
    x1 = jnp.clip(boxes[..., 0::4], 0.0, im_w - 1.0)
    y1 = jnp.clip(boxes[..., 1::4], 0.0, im_h - 1.0)
    x2 = jnp.clip(boxes[..., 2::4], 0.0, im_w - 1.0)
    y2 = jnp.clip(boxes[..., 3::4], 0.0, im_h - 1.0)
    out = jnp.stack([x1, y1, x2, y2], axis=-1)
    return out.reshape(boxes.shape)


# Detectron2's Box2BoxTransform scale_clamp: exp(dw) stays under 1000 / 16
BBOX_XFORM_CLIP = math.log(1000.0 / 16.0)


def refine_boxes(boxes: jnp.ndarray, deltas: jnp.ndarray, im_h,
                 im_w) -> jnp.ndarray:
    """A RoI cascade's step (Cai & Vasconcelos 2019): class-agnostic (…, 4)
    raw deltas applied to (…, 4) boxes by :func:`bbox_pred`, dw and dh held
    under ``BBOX_XFORM_CLIP`` first, then :func:`clip_boxes` to the image.
    No box is dropped: every RoI goes on to the next stage."""
    dxy = deltas[..., :2]
    dwh = jnp.minimum(deltas[..., 2:], BBOX_XFORM_CLIP)
    return clip_boxes(bbox_pred(boxes, jnp.concatenate([dxy, dwh], -1)),
                      im_h, im_w)


def bbox_overlaps(boxes: jnp.ndarray, query_boxes: jnp.ndarray) -> jnp.ndarray:
    """(N, K) IoU matrix (reference: bbox_overlaps_cython).

    On TPU this lowers to broadcast elementwise ops — bandwidth-bound, fused
    by XLA; no custom kernel needed at the sizes the pipeline uses.
    """
    b = boxes[:, None, :]  # (N, 1, 4)
    q = query_boxes[None, :, :]  # (1, K, 4)

    iw = jnp.minimum(b[..., 2], q[..., 2]) - jnp.maximum(b[..., 0], q[..., 0]) + 1.0
    ih = jnp.minimum(b[..., 3], q[..., 3]) - jnp.maximum(b[..., 1], q[..., 1]) + 1.0
    iw = jnp.maximum(iw, 0.0)
    ih = jnp.maximum(ih, 0.0)
    inter = iw * ih

    area_b = (b[..., 2] - b[..., 0] + 1.0) * (b[..., 3] - b[..., 1] + 1.0)
    area_q = (q[..., 2] - q[..., 0] + 1.0) * (q[..., 3] - q[..., 1] + 1.0)
    union = area_b + area_q - inter
    return inter / jnp.maximum(union, _EPS)
