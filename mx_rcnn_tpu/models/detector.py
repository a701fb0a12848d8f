"""Faster R-CNN detector assembly — the reference's train/test symbol graphs.

Maps the reference graphs (``rcnn/symbol/symbol_resnet.py:get_resnet_train``
/ ``get_resnet_test``, ``symbol_vgg.py`` equivalents) onto one flax module:

    backbone conv body → RPN head
      → propose (the ``Proposal`` op — jitted in-graph, stop_gradient)
      → sample_rois (the ``ProposalTarget`` CustomOp — jitted in-graph,
        on-device; kills the reference's per-step device→host→device sync,
        SURVEY §3.1 hot-loop stall)
      → roi_align (the CUDA ``ROIPooling`` — here a dense static-grid
        bilinear gather, Pallas kernel optional)
      → head body (VGG fc6/7 or ResNet stage5) → cls_score / bbox_pred
      → masked losses (losses.py)

Everything is batched per-image with ``jax.vmap`` — static shapes
throughout: post-NMS RoI count and sampled-RoI count are the reference's
own padding contract (2000 train / 300 test / 128 sampled).

Train-time RNG: one key per step, split per image, for anchor subsampling
and RoI sampling (reference used host numpy RNG — SURVEY §7 hard-part 3:
parity is statistical, not bitwise).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models import losses as L
from mx_rcnn_tpu.models.backbones import ResNetConv, ResNetStage5, VGGConv, VGGFC
from mx_rcnn_tpu.models.heads import RCNNOutput, RPNHead
from mx_rcnn_tpu.ops import all_anchors, generate_anchors, assign_anchor, propose, sample_rois
from mx_rcnn_tpu.ops.roi_align import roi_align


class FasterRCNN(nn.Module):
    """Single-level (non-FPN) Faster R-CNN: resnet50/101 or vgg16."""

    cfg: Config

    def setup(self):
        net = self.cfg.network
        dtype = jnp.bfloat16 if self.cfg.tpu.COMPUTE_DTYPE == "bfloat16" else jnp.float32
        self._dtype = dtype
        if net.NETWORK.startswith("resnet"):
            self.backbone = ResNetConv(depth=net.NETWORK, dtype=dtype,
                                       remat=self.cfg.tpu.REMAT_BACKBONE)
            self.head_body = ResNetStage5(depth=net.NETWORK, dtype=dtype)
            self._pooled = 14  # reference: ROIPooling 14×14 → stage5 stride 2 → 7×7
        elif net.NETWORK == "vgg16":
            self.backbone = VGGConv(dtype=dtype)
            self.head_body = VGGFC(dtype=dtype)
            self._pooled = 7
        else:
            raise ValueError(f"unknown backbone {net.NETWORK}")
        self.rpn = RPNHead(num_anchors=net.NUM_ANCHORS, dtype=dtype)
        self.rcnn_out = RCNNOutput(num_classes=self.cfg.NUM_CLASSES, dtype=dtype)

    # ---- shared pieces -----------------------------------------------------

    def _anchors_for(self, feat_h: int, feat_w: int) -> jnp.ndarray:
        """All anchors for a (static) feature shape — numpy at trace time,
        a constant in the compiled program."""
        net = self.cfg.network
        base = generate_anchors(base_size=net.RPN_FEAT_STRIDE,
                                ratios=net.ANCHOR_RATIOS, scales=net.ANCHOR_SCALES)
        return jnp.asarray(all_anchors(feat_h, feat_w, net.RPN_FEAT_STRIDE, base))

    def _rcnn_head(self, feat: jnp.ndarray, rois: jnp.ndarray, deterministic: bool = True):
        """feat: (B, Hf, Wf, C); rois: (B, R, 4) image coords → (B, R, K), (B, R, 4K)."""
        scale = 1.0 / self.cfg.network.RCNN_FEAT_STRIDE
        sr = self.cfg.tpu.ROI_SAMPLING_RATIO
        pooled = jax.vmap(
            lambda f, r: roi_align(f.astype(self._dtype), r, spatial_scale=scale,
                                   pooled_size=self._pooled, sampling_ratio=sr,
                                   mode=self.cfg.tpu.ROI_MODE)
        )(feat, rois)  # (B, R, P, P, C)
        if isinstance(self.head_body, VGGFC):
            emb = self.head_body(pooled, deterministic=deterministic)
        else:
            emb = self.head_body(pooled)
        return self.rcnn_out(emb)

    # ---- train graph (reference get_*_train) -------------------------------

    def __call__(self, images, im_info, gt_boxes, gt_classes, gt_valid, key,
                 gt_masks=None):
        """One training forward pass.

        Args:
          images: (B, H, W, 3) float32, pixel-mean subtracted, padded.
          im_info: (B, 3) float32 — (effective_h, effective_w, scale).
          gt_boxes: (B, G, 4); gt_classes: (B, G) int32; gt_valid: (B, G) bool.
          key: PRNG key for in-graph sampling.
          gt_masks: accepted for loader compatibility; the classic graph has
            no mask head and ignores it (FPN variant consumes it).

        Returns (total_loss, aux) with the six reference metrics' raw pieces.
        """
        del gt_masks
        cfg = self.cfg
        tr = cfg.TRAIN
        B = images.shape[0]

        feat = self.backbone(images)
        fh, fw = feat.shape[1], feat.shape[2]
        anchors = self._anchors_for(fh, fw)
        rpn_cls, rpn_bbox = self.rpn(feat)  # (B, N, 2), (B, N, 4)

        keys = jax.random.split(key, (B, 2))  # works for typed and legacy keys

        # --- RPN targets (in-graph assign_anchor) ---
        assign = jax.vmap(
            lambda gtb, gtv, info, k: assign_anchor(
                anchors, gtb, gtv, info[0], info[1], k,
                batch_size=tr.RPN_BATCH_SIZE, fg_fraction=tr.RPN_FG_FRACTION,
                pos_overlap=tr.RPN_POSITIVE_OVERLAP, neg_overlap=tr.RPN_NEGATIVE_OVERLAP,
                allowed_border=tr.RPN_ALLOWED_BORDER,
                clobber_positives=tr.RPN_CLOBBER_POSITIVES,
                iou_bf16=tr.RPN_ASSIGN_IOU_BF16,
                fused=self.cfg.tpu.ASSIGN_FUSED)
        )(gt_boxes, gt_valid, im_info, keys[:, 0])

        # --- proposals (Proposal op; non-differentiable by contract) ---
        fg_score = L.fg_prob(rpn_cls)
        fg_score = jax.lax.stop_gradient(fg_score)
        rpn_bbox_sg = jax.lax.stop_gradient(rpn_bbox)
        rois, _, roi_valid = jax.vmap(
            lambda s, d, info: propose(
                s, d, anchors, info[0], info[1], info[2],
                pre_nms_top_n=tr.RPN_PRE_NMS_TOP_N, post_nms_top_n=tr.RPN_POST_NMS_TOP_N,
                nms_thresh=tr.RPN_NMS_THRESH, min_size=tr.RPN_MIN_SIZE,
                use_pallas=tr.CXX_PROPOSAL)
        )(fg_score, rpn_bbox_sg, im_info)

        # --- ProposalTarget: append gt, sample 128 RoIs with targets ---
        rois_aug = jnp.concatenate([rois, gt_boxes], axis=1)
        valid_aug = jnp.concatenate([roi_valid, gt_valid], axis=1)
        tgt = jax.vmap(
            lambda r, v, gtb, gtc, gtv, k: sample_rois(
                r, v, gtb, gtc, gtv, k,
                num_classes=cfg.NUM_CLASSES, batch_rois=tr.BATCH_ROIS,
                fg_fraction=tr.FG_FRACTION, fg_thresh=tr.FG_THRESH,
                bg_thresh_hi=tr.BG_THRESH_HI, bg_thresh_lo=tr.BG_THRESH_LO,
                bbox_means=tr.BBOX_MEANS, bbox_stds=tr.BBOX_STDS)
        )(rois_aug, valid_aug, gt_boxes, gt_classes, gt_valid, keys[:, 1])
        tgt = jax.tree.map(jax.lax.stop_gradient, tgt)

        # --- RCNN head ---
        cls_logits, bbox_out = self._rcnn_head(feat, tgt["rois"], deterministic=False)

        # --- losses (reference loss-op semantics, explicit masks) ---
        rpn_cls_loss = L.softmax_ce_ignore(rpn_cls, assign["label"])
        rpn_bbox_loss = L.smooth_l1(rpn_bbox, assign["bbox_target"],
                                    assign["bbox_weight"], sigma=3.0,
                                    norm=float(tr.RPN_BATCH_SIZE) * B)
        rcnn_cls_loss = L.softmax_ce_weighted(cls_logits, tgt["label"], tgt["label_weight"])
        rcnn_bbox_loss = L.smooth_l1(bbox_out, tgt["bbox_target"], tgt["bbox_weight"],
                                     sigma=1.0, norm=float(tr.BATCH_ROIS) * B)
        total = rpn_cls_loss + rpn_bbox_loss + rcnn_cls_loss + rcnn_bbox_loss

        aux = {
            "rpn_cls_loss": rpn_cls_loss,
            "rpn_bbox_loss": rpn_bbox_loss,
            "rcnn_cls_loss": rcnn_cls_loss,
            "rcnn_bbox_loss": rcnn_bbox_loss,
            # raw pieces for the six reference metrics (core/metric.py)
            "rpn_label": assign["label"],
            "rpn_pred": jnp.argmax(rpn_cls, axis=-1),
            "rcnn_label": tgt["label"],
            "rcnn_pred": jnp.argmax(cls_logits, axis=-1),
            "rcnn_label_weight": tgt["label_weight"],
        }
        return total, aux

    # ---- test graph (reference get_*_test) ---------------------------------

    def predict(self, images, im_info):
        """Inference forward:
        (rois, roi_valid, cls_prob, bbox_deltas, roi_scores).

        rois are in the *scaled* image frame, like the reference's test
        symbol; the eval layer divides by im_scale (tester.py im_detect).
        """
        cfg = self.cfg
        te = cfg.TEST
        feat = self.backbone(images)
        anchors = self._anchors_for(feat.shape[1], feat.shape[2])
        rpn_cls, rpn_bbox = self.rpn(feat)
        fg_score = L.fg_prob(rpn_cls)
        rois, roi_scores, roi_valid = jax.vmap(
            lambda s, d, info: propose(
                s, d, anchors, info[0], info[1], info[2],
                pre_nms_top_n=te.RPN_PRE_NMS_TOP_N, post_nms_top_n=te.RPN_POST_NMS_TOP_N,
                nms_thresh=te.RPN_NMS_THRESH, min_size=te.RPN_MIN_SIZE,
                use_pallas=te.CXX_PROPOSAL)
        )(fg_score, rpn_bbox, im_info)
        cls_logits, bbox_deltas = self._rcnn_head(feat, rois, deterministic=True)
        cls_prob = jax.nn.softmax(cls_logits, axis=-1)
        return rois, roi_valid, cls_prob, bbox_deltas, roi_scores

    def predict_rpn(self, images, im_info):
        """RPN-only inference (reference ``get_*_rpn_test``) — proposal
        generation for 4-step alternate training (tester.generate_proposals)."""
        te = self.cfg.TEST
        feat = self.backbone(images)
        anchors = self._anchors_for(feat.shape[1], feat.shape[2])
        rpn_cls, rpn_bbox = self.rpn(feat)
        fg_score = L.fg_prob(rpn_cls)
        return jax.vmap(
            lambda s, d, info: propose(
                s, d, anchors, info[0], info[1], info[2],
                pre_nms_top_n=te.RPN_PRE_NMS_TOP_N, post_nms_top_n=te.RPN_POST_NMS_TOP_N,
                nms_thresh=te.RPN_NMS_THRESH, min_size=te.RPN_MIN_SIZE,
                use_pallas=te.CXX_PROPOSAL)
        )(fg_score, rpn_bbox, im_info)

    def rpn_train(self, images, im_info, gt_boxes, gt_valid, key):
        """RPN-only training graph (reference ``get_*_rpn`` — alternate
        training steps 1 and 4)."""
        tr = self.cfg.TRAIN
        B = images.shape[0]
        feat = self.backbone(images)
        anchors = self._anchors_for(feat.shape[1], feat.shape[2])
        rpn_cls, rpn_bbox = self.rpn(feat)
        keys = jax.random.split(key, B)
        assign = jax.vmap(
            lambda gtb, gtv, info, k: assign_anchor(
                anchors, gtb, gtv, info[0], info[1], k,
                batch_size=tr.RPN_BATCH_SIZE, fg_fraction=tr.RPN_FG_FRACTION,
                pos_overlap=tr.RPN_POSITIVE_OVERLAP, neg_overlap=tr.RPN_NEGATIVE_OVERLAP,
                allowed_border=tr.RPN_ALLOWED_BORDER,
                clobber_positives=tr.RPN_CLOBBER_POSITIVES,
                iou_bf16=tr.RPN_ASSIGN_IOU_BF16,
                fused=self.cfg.tpu.ASSIGN_FUSED)
        )(gt_boxes, gt_valid, im_info, keys)
        rpn_cls_loss = L.softmax_ce_ignore(rpn_cls, assign["label"])
        rpn_bbox_loss = L.smooth_l1(rpn_bbox, assign["bbox_target"],
                                    assign["bbox_weight"], sigma=3.0,
                                    norm=float(tr.RPN_BATCH_SIZE) * B)
        total = rpn_cls_loss + rpn_bbox_loss
        aux = {"rpn_cls_loss": rpn_cls_loss, "rpn_bbox_loss": rpn_bbox_loss,
               "rpn_label": assign["label"], "rpn_pred": jnp.argmax(rpn_cls, axis=-1)}
        return total, aux

    def rcnn_train(self, images, im_info, rois, roi_valid, gt_boxes, gt_classes,
                   gt_valid, key):
        """Fast-RCNN training graph on externally supplied proposals
        (reference ``get_*_rcnn`` + ROIIter — alternate training steps 3/6)."""
        cfg = self.cfg
        tr = cfg.TRAIN
        B = images.shape[0]
        feat = self.backbone(images)
        keys = jax.random.split(key, B)
        rois_aug = jnp.concatenate([rois, gt_boxes], axis=1)
        valid_aug = jnp.concatenate([roi_valid, gt_valid], axis=1)
        tgt = jax.vmap(
            lambda r, v, gtb, gtc, gtv, k: sample_rois(
                r, v, gtb, gtc, gtv, k,
                num_classes=cfg.NUM_CLASSES, batch_rois=tr.BATCH_ROIS,
                fg_fraction=tr.FG_FRACTION, fg_thresh=tr.FG_THRESH,
                bg_thresh_hi=tr.BG_THRESH_HI, bg_thresh_lo=tr.BG_THRESH_LO,
                bbox_means=tr.BBOX_MEANS, bbox_stds=tr.BBOX_STDS)
        )(rois_aug, valid_aug, gt_boxes, gt_classes, gt_valid, keys)
        tgt = jax.tree.map(jax.lax.stop_gradient, tgt)
        cls_logits, bbox_out = self._rcnn_head(feat, tgt["rois"], deterministic=False)
        rcnn_cls_loss = L.softmax_ce_weighted(cls_logits, tgt["label"], tgt["label_weight"])
        rcnn_bbox_loss = L.smooth_l1(bbox_out, tgt["bbox_target"], tgt["bbox_weight"],
                                     sigma=1.0, norm=float(tr.BATCH_ROIS) * B)
        total = rcnn_cls_loss + rcnn_bbox_loss
        aux = {"rcnn_cls_loss": rcnn_cls_loss, "rcnn_bbox_loss": rcnn_bbox_loss,
               "rcnn_label": tgt["label"], "rcnn_pred": jnp.argmax(cls_logits, axis=-1),
               "rcnn_label_weight": tgt["label_weight"]}
        return total, aux


def build_model(cfg: Config) -> FasterRCNN:
    """Factory — the analogue of the reference's ``get_<net>_train/test``
    symbol selectors (dispatch in train_end2end.py / test.py)."""
    if cfg.network.HAS_FPN:
        from mx_rcnn_tpu.models.fpn import FPNFasterRCNN

        return FPNFasterRCNN(cfg=cfg)
    return FasterRCNN(cfg=cfg)


def init_params(model: FasterRCNN, cfg: Config, key, batch_size: int = 1,
                image_hw: Optional[tuple] = None):
    """Initialize parameters with a dummy batch (shapes from the first scale
    bucket).  Returns the params pytree."""
    if image_hw is None:
        s = cfg.tpu.SCALES[0]
        stride = max(cfg.network.IMAGE_STRIDE, cfg.network.RPN_FEAT_STRIDE)
        image_hw = (int(np.ceil(s[0] / stride) * stride),
                    int(np.ceil(s[1] / stride) * stride))
    h, w = image_hw
    g = cfg.tpu.MAX_GT
    k1, k2 = jax.random.split(key)
    kwargs = {}
    if cfg.network.HAS_MASK:
        from mx_rcnn_tpu.data.mask import GT_MASK_SIZE

        # mask_head params only materialize if the mask branch traces at init
        kwargs["gt_masks"] = jnp.zeros(
            (batch_size, g, GT_MASK_SIZE, GT_MASK_SIZE), jnp.float32)
    dummy = dict(
        images=jnp.zeros((batch_size, h, w, 3), jnp.float32),
        im_info=jnp.tile(jnp.asarray([[h, w, 1.0]], jnp.float32), (batch_size, 1)),
        gt_boxes=jnp.zeros((batch_size, g, 4), jnp.float32),
        gt_classes=jnp.zeros((batch_size, g), jnp.int32),
        gt_valid=jnp.zeros((batch_size, g), bool),
    )
    # jit the init: eager flax init dispatches the whole train graph op by
    # op, one compile and one launch each, at full image scale
    init_fn = jax.jit(partial(model.init, **kwargs))
    variables = init_fn({"params": k1, "dropout": k2}, dummy["images"],
                        dummy["im_info"], dummy["gt_boxes"],
                        dummy["gt_classes"], dummy["gt_valid"], k2)
    return variables["params"]
