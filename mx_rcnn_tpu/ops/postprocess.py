"""Shared host post-process: decode → clip → per-class NMS → max_per_image.

This is the block every inference consumer runs after the device forward —
``pred_eval``'s dataset loop, the online serve engine, and any future
batch-prediction tool.  It used to live inline in ``eval/tester.py``; the
serve subsystem needs the exact same math (a drifted copy would make served
detections disagree with the eval metric for the same weights), so the
single source of truth lives here and a parity test pins it to the
reference block's semantics (``tests/test_serve.py``).

All on the host — identical accounting to the reference's ``pred_eval``
(per-class score threshold → NMS → global per-image cap).  The decode, the
cap and the split by class are numpy; the threshold, the gather by class
and every class's greedy NMS are one call an image into the native library
(``native.nms_classes`` → ``mxr_nms_classes``), with the Python loop over
``native.nms`` (numpy without the library) kept as the fallback and the
oracle: 80 trips through the interpreter and the GIL an image were the
largest stage of a serving turn.  Nothing outside
:func:`device_postprocess` touches a device array, the device or a compiled
program: :func:`decode_image_boxes` is the host twin of the in-graph
``ops.boxes.bbox_pred`` + ``clip_boxes`` (``tests/test_postprocess.py`` ties
the two and holds this one to the host).  The in-graph pair, called on the
arrays a readback has just brought to the host, ships them to the device
again and runs a dozen one-op programs eagerly: 40 ms an image on a TPU for
arithmetic numpy does in under one.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np


def decode_image_boxes(rois: np.ndarray, deltas: np.ndarray,
                       im_info_row) -> np.ndarray:
    """One image's raw RPN rois + head deltas → (R, 4K) boxes in ORIGINAL
    image coordinates (reference ``im_detect``: bbox_pred + clip_boxes,
    then divide by im_scale).  ``im_info_row`` is the (eh, ew, scale)
    triple the loader ships.

    Host numpy only, float32 whatever the inputs' dtype (a bfloat16
    readback is cast up first); same formulas in the same order as
    ``ops.boxes.bbox_pred`` / ``clip_boxes``, legacy "+1" widths included.
    Returns a C-contiguous float32 array."""
    rois = np.asarray(rois, np.float32)
    deltas = np.asarray(deltas, np.float32)
    eh, ew, s = np.asarray(im_info_row, np.float32)
    d = deltas.reshape(len(deltas), -1, 4)               # (R, K, 4)

    w = rois[:, 2:3] - rois[:, 0:1] + 1.0                # (R, 1)
    h = rois[:, 3:4] - rois[:, 1:2] + 1.0
    cx = rois[:, 0:1] + 0.5 * (w - 1.0)
    cy = rois[:, 1:2] + 0.5 * (h - 1.0)

    pred_cx = d[:, :, 0] * w + cx                        # (R, K)
    pred_cy = d[:, :, 1] * h + cy
    half_w = 0.5 * (np.exp(d[:, :, 2]) * w - 1.0)        # 0.5 (pred_w - 1)
    half_h = 0.5 * (np.exp(d[:, :, 3]) * h - 1.0)

    x_max, y_max = ew - np.float32(1.0), eh - np.float32(1.0)
    out = np.empty(d.shape, np.float32)
    np.clip(pred_cx - half_w, 0.0, x_max, out=out[:, :, 0])
    np.clip(pred_cy - half_h, 0.0, y_max, out=out[:, :, 1])
    np.clip(pred_cx + half_w, 0.0, x_max, out=out[:, :, 2])
    np.clip(pred_cy + half_h, 0.0, y_max, out=out[:, :, 3])
    out /= s
    return out.reshape(deltas.shape)


def per_class_nms(scores: np.ndarray, boxes: np.ndarray, valid,
                  num_classes: int, thresh: float, nms_thresh: float,
                  max_per_image: int, nms_fn=None) -> List[Optional[np.ndarray]]:
    """One image's (R, K) scores + (R, 4K) original-frame boxes →
    per-class (N, 5) [x1,y1,x2,y2,score] detections (reference
    ``pred_eval`` inner block: per-class score threshold → NMS → global
    per-image score cap).

    Returns a list indexed by class; index 0 (background) is ``None``.
    With no ``nms_fn`` and the native library loaded, the threshold, the
    gather and every class's NMS are ONE foreign call
    (``native.nms_classes``); the cap and the split by class are a few
    numpy calls on its flat rows.  With an ``nms_fn`` injected (oracle
    tests) or no library, the loop below runs, a ``nms_fn`` call a class:
    the fallback, and the oracle the native call is held to row for row."""
    if nms_fn is None:
        from mx_rcnn_tpu import native

        rows = native.nms_classes(scores[:, :num_classes],
                                  boxes[:, :4 * num_classes], valid,
                                  thresh, nms_thresh)
        if rows is not None:
            # cap total detections per image (reference max_per_image block)
            if 0 < max_per_image < len(rows):
                th = np.sort(rows[:, 4])[-max_per_image]
                rows = rows[rows[:, 4] >= th]
            # rows come sorted by class: class k is rows[at[k-1]:at[k]]
            at = np.searchsorted(rows[:, 5], np.arange(1, num_classes + 1))
            rows = np.ascontiguousarray(rows[:, :5])
            return [None] + [rows[a:b] for a, b in zip(at[:-1], at[1:])]
        nms_fn = native.nms
    v = np.asarray(valid, bool)
    dets: List[Optional[np.ndarray]] = [None] * num_classes
    for k in range(1, num_classes):
        sel = (scores[:, k] > thresh) & v
        cls_scores = scores[sel, k]
        cls_boxes = boxes[sel, 4 * k:4 * (k + 1)]
        cls_dets = np.hstack([cls_boxes, cls_scores[:, None]]).astype(
            np.float32)
        keep = nms_fn(cls_dets, nms_thresh)
        dets[k] = cls_dets[keep]
    # cap total detections per image (reference max_per_image block)
    if max_per_image > 0:
        scores_all = np.concatenate(
            [dets[k][:, 4] for k in range(1, num_classes)])
        if len(scores_all) > max_per_image:
            th = np.sort(scores_all)[-max_per_image]
            for k in range(1, num_classes):
                dets[k] = dets[k][dets[k][:, 4] >= th]
    return dets


def device_postprocess(rois, roi_valid, cls_prob, bbox_deltas, im_info, *,
                       num_classes: int, thresh: float, nms_thresh: float,
                       max_per_image: int, per_class_max: Optional[int] = None,
                       use_pallas: bool = False):
    """The jit-traceable fusion of :func:`decode_image_boxes` +
    :func:`per_class_nms` — the ``--device-postprocess`` readback shrink.

    Runs inside the ``predict_post`` program right after the forward, so
    the host reads back ``(B, cap, 6)`` final detections instead of the
    full ``(R, K)`` scores + ``(R, 4K)`` deltas.  Per image: decode + clip
    to the scaled frame, map to ORIGINAL coordinates, per-class score
    threshold → greedy NMS (``ops.nms.nms_ranked``; ``use_pallas`` routes
    the TPU bitmask kernel), then the global top-``max_per_image`` cap
    over all classes.

    Semantics match the host path with one documented exception: the host
    cap keeps every detection tied AT the cut-off score (``>= th`` can
    exceed ``max_per_image``), while ``lax.top_k`` keeps exactly
    ``max_per_image`` rows — exact score ties at the cap boundary may
    differ.  The parity test pins everything else.

    Returns:
      dets: (B, cap, 6) float32 [x1,y1,x2,y2,score,cls], score-descending;
        padded rows zeroed.
      valid: (B, cap) bool.
    """
    import jax
    import jax.numpy as jnp

    from mx_rcnn_tpu.ops.boxes import bbox_pred, clip_boxes

    NEG = -1e10
    R = rois.shape[1]
    K = num_classes
    pcm = per_class_max or (max_per_image if max_per_image > 0 else R)
    cap = max_per_image if max_per_image > 0 else (K - 1) * pcm
    cap = min(cap, (K - 1) * pcm)

    def one_image(rois_i, valid_i, scores_i, deltas_i, info_i):
        from mx_rcnn_tpu.ops.nms import nms_ranked

        boxes = bbox_pred(rois_i, deltas_i)
        boxes = clip_boxes(boxes, info_i[0], info_i[1]) / info_i[2]
        boxes_k = boxes.reshape(R, K, 4).transpose(1, 0, 2)[1:]  # (K-1, R, 4)
        scores_k = scores_i.T[1:]                                # (K-1, R)
        sel_k = (scores_k > thresh) & valid_i[None, :].astype(bool)
        dets_k, mask_k = jax.vmap(
            lambda b, s, v: nms_ranked(b, s, pcm, nms_thresh, valid=v,
                                       use_pallas=use_pallas))(
            boxes_k, scores_k, sel_k)            # (K-1, pcm, 5) / (K-1, pcm)
        flat = dets_k.reshape(-1, 5)
        fscore = jnp.where(mask_k.reshape(-1), flat[:, 4], NEG)
        top_s, top_i = jax.lax.top_k(fscore, cap)
        cls = (top_i // pcm + 1).astype(jnp.float32)
        out = jnp.concatenate([flat[top_i], cls[:, None]], axis=1)
        dvalid = top_s > NEG / 2
        return jnp.where(dvalid[:, None], out, 0.0), dvalid

    return jax.vmap(one_image)(rois, roi_valid, cls_prob, bbox_deltas,
                               im_info)


def device_dets_to_per_class(dets: np.ndarray, valid,
                             num_classes: int) -> List[Optional[np.ndarray]]:
    """One image's :func:`device_postprocess` readback → the per-class
    ``[None, (N1,5), ...]`` shape :func:`per_class_nms` returns, so
    ``all_boxes`` filling (and everything downstream — mask pass, vis,
    det_cache, scoring) is path-agnostic.  Rows arrive score-descending
    from the device top-k, which is exactly the host NMS keep order
    within a class."""
    dets = np.asarray(dets, np.float32)
    v = np.asarray(valid, bool)
    rows = dets[v]
    out: List[Optional[np.ndarray]] = [None] * num_classes
    for k in range(1, num_classes):
        out[k] = np.ascontiguousarray(rows[rows[:, 5] == k][:, :5],
                                      np.float32)
    return out


def detections_to_records(dets_per_class) -> List[dict]:
    """Per-class (N, 5) arrays → flat JSON-serializable records sorted by
    descending score — the serve response payload shape."""
    out = []
    for k, d in enumerate(dets_per_class):
        if not k or d is None:
            continue
        for row in d:
            out.append({"cls": int(k), "score": float(row[4]),
                        "bbox": [float(c) for c in row[:4]]})
    out.sort(key=lambda r: -r["score"])
    return out
