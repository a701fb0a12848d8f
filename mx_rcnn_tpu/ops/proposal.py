"""Proposal generation (RPN output → RoIs) — the reference's ``Proposal`` op.

Behavioral contract (rcnn/symbol/proposal.py CustomOp, and MXNet's C++/CUDA
``mx.contrib.sym.Proposal`` selected by config.CXX_PROPOSAL):

1. decode per-anchor deltas into boxes (bbox_pred), clip to the image;
2. drop boxes smaller than min_size · im_scale on either side;
3. keep the top pre_nms_top_n by fg score (12000 train / 6000 test);
4. greedy NMS at 0.7;
5. keep the top post_nms_top_n (2000 train / 300 test), padding the output
   to that static size — the reference pads by duplicating kept boxes
   (npr.choice over keep); we return an explicit validity mask instead and
   duplicate-pad, which downstream masked ops consume directly.

Non-differentiable by contract (reference backward is zeros): callers wrap
the output in ``stop_gradient``.

This is a jitted device-side op; the NMS inside is ``ops.nms.nms_padded``
(pure JAX) or the Pallas bitmask kernel (kernels/nms_pallas.py) chosen by
``use_pallas``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from mx_rcnn_tpu.ops.boxes import bbox_pred, clip_boxes
from mx_rcnn_tpu.ops.nms import nms_padded


@partial(jax.jit, static_argnames=("pre_nms_top_n", "post_nms_top_n", "nms_thresh",
                                   "min_size", "use_pallas"))
def propose(
    scores: jnp.ndarray,
    bbox_deltas: jnp.ndarray,
    anchors: jnp.ndarray,
    im_h: jnp.ndarray,
    im_w: jnp.ndarray,
    im_scale: jnp.ndarray,
    *,
    pre_nms_top_n: int = 6000,
    post_nms_top_n: int = 300,
    nms_thresh: float = 0.7,
    min_size: int = 16,
    use_pallas: bool = False,
):
    """Generate proposals for one image.

    Args:
      scores: (N,) per-anchor foreground probability (already sliced from the
        2-way softmax, matching the reference's ``scores[:, A:, :, :]``).
      bbox_deltas: (N, 4) per-anchor regression output.
      anchors: (N, 4) anchor boxes for this feature shape.
      im_h, im_w, im_scale: effective image size and resize scale (traced).

    Returns:
      rois: (post_nms_top_n, 4) float32, duplicate-padded.
      roi_scores: (post_nms_top_n,) float32.
      roi_valid: (post_nms_top_n,) bool.
    """
    n = scores.shape[0]
    boxes = bbox_pred(anchors, bbox_deltas)
    boxes = clip_boxes(boxes, im_h, im_w)

    ws = boxes[:, 2] - boxes[:, 0] + 1.0
    hs = boxes[:, 3] - boxes[:, 1] + 1.0
    ms = min_size * im_scale
    size_ok = (ws >= ms) & (hs >= ms)
    scores = jnp.where(size_ok, scores, -1.0)

    k = min(pre_nms_top_n, n)
    top_scores, top_idx = jax.lax.top_k(scores, k)
    top_boxes = boxes[top_idx]
    top_valid = top_scores > -0.5

    if use_pallas:
        from mx_rcnn_tpu.kernels.nms_pallas import nms_pallas
        keep_idx, keep_mask = nms_pallas(
            top_boxes, top_scores, max_out=post_nms_top_n,
            iou_thresh=nms_thresh, valid=top_valid)
    else:
        keep_idx, keep_mask = nms_padded(
            top_boxes, top_scores, max_out=post_nms_top_n,
            iou_thresh=nms_thresh, valid=top_valid)

    rois = top_boxes[keep_idx]
    roi_scores = jnp.where(keep_mask, top_scores[keep_idx], 0.0)
    # duplicate-pad: invalid slots point at keep_idx 0 (the top box) already,
    # because nms_padded emits index 0 for empty slots; mask tells the truth.
    return rois, roi_scores, keep_mask


def _level_topk(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """Exact top-k indices of a flat score vector, shaped to dodge the v5e
    windowed-TopK emitter bug (see the crash ledger at the call site).

    Two-stage: reshape to (G, n/G) rows, take top-k per row (every global
    top-k element is in its row's top-k, so the union is a superset), then
    top-k over the G·k survivors.  Both stages see row lengths far below
    the crashing (1, 116736) shape.  Order within ties differs from
    argsort — irrelevant at the call site (candidates are re-sorted
    jointly).  Falls back to argsort when the vector is too small to
    split.
    """
    n = scores.shape[0]
    # largest split with whole rows no shorter than k.  Shapes that run:
    # train at 608×1024, k=2400 — P2 @ 116736 → g=16, P3 @ 29184 → g=8,
    # smaller levels fall back to argsort; the served test contract at
    # 800×1344, k=1000 (benchmark cell fpn-serve-closed) — P2 @ 201600 →
    # g=16 (rows of 12600), P3 @ 50400 → g=16 (3150), P4 @ 12600 → g=8
    # (1575; 16 does not divide it), P5 @ 3150 → g=2 (1575), P6 @ 819 <
    # k → the caller passes k=819 and the argsort branch takes it whole.
    # All of them compile for a described v5e in tests/test_tpu_kernels.py
    # and run on the chip in that cell.
    g = next((g for g in (16, 8, 4, 2) if n % g == 0 and n // g >= k), 1)
    if g == 1:
        return jnp.argsort(-scores)[:k]
    rows = scores.reshape(g, n // g)
    v1, i1 = jax.lax.top_k(rows, k)                      # (G, k) per-row
    base = (jnp.arange(g, dtype=jnp.int32) * (n // g))[:, None]
    flat_idx = (i1 + base).reshape(-1)                   # (G·k,)
    _, i2 = jax.lax.top_k(v1.reshape(-1), k)             # exact global k
    return flat_idx[i2]


def propose_fpn(
    level_scores,
    level_deltas,
    level_anchors,
    im_h,
    im_w,
    im_scale,
    *,
    pre_nms_top_n: int = 12000,
    post_nms_top_n: int = 2000,
    nms_thresh: float = 0.7,
    min_size: int = 16,
    use_pallas: bool = False,
):
    """Multi-level proposal generation (FPN): per-level decode + top-k
    (pre_nms_top_n split evenly across levels, the Detectron per-level cap),
    concat, then ONE joint NMS to post_nms_top_n.

    Args are parallel lists over pyramid levels; same per-image contract and
    return shape as ``propose``.
    """
    nl = len(level_scores)
    k_level = max(pre_nms_top_n // nl, 1)
    cand_boxes, cand_scores = [], []
    for scores, deltas, anchors in zip(level_scores, level_deltas,
                                       level_anchors):
        boxes = bbox_pred(anchors, deltas)
        boxes = clip_boxes(boxes, im_h, im_w)
        ws = boxes[:, 2] - boxes[:, 0] + 1.0
        hs = boxes[:, 3] - boxes[:, 1] + 1.0
        ms = min_size * im_scale
        scores = jnp.where((ws >= ms) & (hs >= ms), scores, -1.0)
        k = min(k_level, scores.shape[0])
        # argsort instead of lax.top_k — v5e compiler-bug fence, widened in
        # round 3.  Crash ledger (all in the full FPN train graph; each
        # works standalone):
        #   * lax.top_k (round 2, jax 0.9.0): `F fusion_util.cc:3726 Check
        #     failed: chunk_counts[new_window_dim] == 1 ... TransformWindow
        #     ... f32[1,116736,1]` → SIGABRT.
        #   * approx_max_k(recall_target=1.0) (round 3):
        #     `TopkEmitter::EmitBatchForWindowedR2: Check failed:
        #     operand.span_size.RawSize() > 0` → SIGABRT.
        #   * lax.top_k behind jax.lax.optimization_barrier (round 3): same
        #     span_size check in `TopkEmitter::EmitWindowedR2` — the bug is
        #     in the windowed TopK emitter itself at this (1, 116736)/
        #     k=2400 shape, not the fusion pass, so isolation cannot fix
        #     it.  (assign_anchor's top_k survives because its k=256 takes
        #     a different emitter path.)
        # Hence _level_topk's two stages (rows far below that shape; the
        # argsort only for a level too small to split, ~1.3 ms at P2 when
        # it was the whole fence).  Served at 800×1344 the levels hold
        # 201600 / 50400 / 12600 / 3150 / 819 anchors at k_level=1000: P6
        # has fewer than k, so k=819 there and the joint NMS below sees
        # 4×1000 + 819 = 4819.  Retry the ledger on libtpu/jax upgrades.
        top_idx = _level_topk(scores, k)
        cand_boxes.append(boxes[top_idx])
        cand_scores.append(scores[top_idx])
    boxes = jnp.concatenate(cand_boxes, axis=0)
    scores = jnp.concatenate(cand_scores, axis=0)
    # global score sort: each level's top-k is sorted internally but not
    # across levels, and the NMS backends' greedy order (and the Pallas
    # sweep's index order) must be score-descending
    order = jnp.argsort(-scores)
    boxes = boxes[order]
    scores = scores[order]
    valid = scores > -0.5

    if use_pallas:
        from mx_rcnn_tpu.kernels.nms_pallas import nms_pallas
        keep_idx, keep_mask = nms_pallas(boxes, scores, max_out=post_nms_top_n,
                                         iou_thresh=nms_thresh, valid=valid)
    else:
        keep_idx, keep_mask = nms_padded(boxes, scores, max_out=post_nms_top_n,
                                         iou_thresh=nms_thresh, valid=valid)
    rois = boxes[keep_idx]
    roi_scores = jnp.where(keep_mask, scores[keep_idx], 0.0)
    return rois, roi_scores, keep_mask
