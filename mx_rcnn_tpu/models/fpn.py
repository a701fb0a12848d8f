"""FPN Faster/Mask R-CNN (BASELINE.json configs 4–5).

Not part of classic mx-rcnn (SURVEY §0 item 3 — capability target, patterns
from the FPN/Mask R-CNN papers and their standard implementations):

* neck: lateral 1×1 on C2–C5 + nearest top-down + 3×3 smoothing → P2–P5
  (256 ch), P6 = stride-2 subsample of P5 (RPN only).
* RPN: one shared head over all levels; per-level anchors (one scale ×
  3 ratios per level, FPN_ANCHOR_SCALES), per-level top-k then joint NMS.
* RoI features: level assignment k = floor(k0 + log2(√area/224)) clamped to
  P2–P5; static-shape trick — pool every level, select by one-hot (4 cheap
  gathers beat dynamic partitions on TPU).
* head: 2×FC-1024 (the standard FPN box head), cls + bbox.
* mask head (HAS_MASK): 14×14 ROIAlign on the assigned level → 4 convs +
  deconv → 28×28 per-class logits; targets are gt masks resampled into the
  RoI frame in-graph (ops/mask_target.py) from host-rasterized gt-box crops.

Sampling/targets/losses reuse the exact same ops as the classic graph —
behavioral contracts unchanged.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models import losses as L
from mx_rcnn_tpu.models.backbones import ResNetConv
from mx_rcnn_tpu.models.heads import (MaskHead, RCNNOutput, RPNHead,
                                      conv_norm_relu)
from mx_rcnn_tpu.models.vit import SimpleFeaturePyramid, ViT
from mx_rcnn_tpu.ops import (all_anchors, assign_anchor, generate_anchors,
                             propose, sample_rois)
from mx_rcnn_tpu.ops.mask_target import mask_targets_for_rois
from mx_rcnn_tpu.ops.proposal import propose_fpn
from mx_rcnn_tpu.ops.roi_align import roi_align


class FPNNeck(nn.Module):
    out_channels: int = 256
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, c2, c3, c4, c5):
        lat = lambda i, x: nn.Conv(  # noqa: E731
            self.out_channels, (1, 1), dtype=self.dtype, name=f"lateral{i}")(x)
        out = lambda i, x: nn.Conv(  # noqa: E731
            self.out_channels, (3, 3), padding=[(1, 1), (1, 1)],
            dtype=self.dtype, name=f"post{i}")(x)

        p5 = lat(5, c5)
        p4 = lat(4, c4) + _upsample2(p5)
        p3 = lat(3, c3) + _upsample2(p4)
        p2 = lat(2, c2) + _upsample2(p3)
        p2, p3, p4, p5 = out(2, p2), out(3, p3), out(4, p4), out(5, p5)
        p6 = nn.max_pool(p5, (1, 1), strides=(2, 2))  # stride-2 subsample
        return p2, p3, p4, p5, p6


def _upsample2(x):
    b, h, w, c = x.shape
    return jax.image.resize(x, (b, 2 * h, 2 * w, c), method="nearest")


class FPNBoxHead(nn.Module):
    """2×FC-1024 box head (standard FPN head; VGG-style but shared-width)."""

    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        x = x.reshape(x.shape[:-3] + (-1,))
        x = nn.relu(nn.Dense(1024, dtype=self.dtype, name="fc6")(x))
        x = nn.relu(nn.Dense(1024, dtype=self.dtype, name="fc7")(x))
        return x


class ConvFCBoxHead(nn.Module):
    """4 × (3×3 conv 256, LN, ReLU) on the pooled map + FC-1024 (the box
    head of the ViTDet baseline)."""

    channels: int = 256
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        lead = x.shape[:-3]
        x = x.reshape((-1,) + x.shape[-3:])
        for i in range(1, 5):
            x = conv_norm_relu(x, self.channels, f"conv{i}", True, self.dtype)
        x = x.reshape(lead + (-1,))
        return nn.relu(nn.Dense(1024, dtype=self.dtype, name="fc6")(x))


class FPNFasterRCNN(nn.Module):
    """Multi-level two-stage detector; optionally with a mask head.  The
    preset chooses what stands under the five-level pyramid — a ResNet
    with the top-down neck, or a plain ViT with the simple feature pyramid
    (``network.NETWORK``) — and with it the RPN's depth and the heads'
    bodies; from ``_rpn_over_levels`` on nothing knows which trunk ran."""

    cfg: Config

    def setup(self):
        net = self.cfg.network
        dtype = jnp.bfloat16 if self.cfg.tpu.COMPUTE_DTYPE == "bfloat16" else jnp.float32
        self._dtype = dtype
        vit = net.NETWORK == "vit"
        if vit:
            side = set(self.cfg.tpu.SCALES[0])
            assert len(side) == 1 and not side.pop() % (2 * net.VIT_PATCH), \
                f"a ViT trunk takes one square bucket, a multiple of " \
                f"{2 * net.VIT_PATCH} px; tpu.SCALES[0] = {self.cfg.tpu.SCALES[0]}"
            self.backbone = ViT(
                grid=self.cfg.tpu.SCALES[0][0] // net.VIT_PATCH,
                patch=net.VIT_PATCH, width=net.VIT_WIDTH,
                depth=net.VIT_DEPTH, heads=net.VIT_HEADS,
                window=net.VIT_WINDOW, global_blocks=net.VIT_GLOBAL_BLOCKS,
                dtype=dtype)
            self.neck = SimpleFeaturePyramid(
                out_channels=net.FPN_OUT_CHANNELS, dtype=dtype)
        elif net.NETWORK.startswith("resnet"):
            self.backbone = ResNetConv(depth=net.NETWORK, dtype=dtype,
                                       all_stages=True,
                                       remat=self.cfg.tpu.REMAT_BACKBONE)
            self.neck = FPNNeck(out_channels=net.FPN_OUT_CHANNELS,
                                dtype=dtype)
        else:
            raise ValueError(f"no pyramid trunk {net.NETWORK!r}")
        # FPN's shared RPN head is FPN_OUT_CHANNELS (256) wide — the FPN
        # paper/Detectron convention (the classic C4 RPN uses 512); at P2
        # resolution the 3×3 hidden conv is the single most expensive op in
        # the whole step (3.4 ms fwd at 512ch, profiled), so width follows
        # the convention, not the classic default
        self.rpn = RPNHead(num_anchors=net.NUM_ANCHORS,
                           channels=net.FPN_OUT_CHANNELS,
                           convs=2 if vit else 1, dtype=dtype)
        self.head_body = (ConvFCBoxHead(channels=net.FPN_OUT_CHANNELS,
                                        dtype=dtype) if vit
                          else FPNBoxHead(dtype=dtype))
        self.rcnn_out = RCNNOutput(num_classes=self.cfg.NUM_CLASSES, dtype=dtype)
        if net.HAS_MASK:
            self.mask_head = MaskHead(num_classes=self.cfg.NUM_CLASSES,
                                      layer_norm=vit, dtype=dtype)

    # ---- shared pieces -----------------------------------------------------

    @property
    def _strides(self):
        return self.cfg.network.FPN_FEAT_STRIDES  # (4, 8, 16, 32, 64)

    def _pyramid(self, images):
        if self.cfg.network.NETWORK == "vit":
            return self.neck(self.backbone(images))
        return self.neck(*self.backbone(images))

    def _anchors_for_level(self, feat_h: int, feat_w: int, stride: int,
                           scale: int) -> jnp.ndarray:
        net = self.cfg.network
        base = generate_anchors(base_size=stride, ratios=net.ANCHOR_RATIOS,
                                scales=(scale,))
        return jnp.asarray(all_anchors(feat_h, feat_w, stride, base))

    def _rpn_over_levels(self, feats):
        """Shared RPN over P2–P6 → per-level (cls, bbox, anchors)."""
        net = self.cfg.network
        out = []
        for lvl, feat in enumerate(feats):
            stride = self._strides[lvl]
            scale = net.FPN_ANCHOR_SCALES[0]
            cls, bbox = self.rpn(feat)
            anchors = self._anchors_for_level(feat.shape[1], feat.shape[2],
                                              stride, scale)
            out.append((cls, bbox, anchors))
        return out

    def _assign_level(self, rois):
        """(…, 4) rois → level index 0..3 (P2..P5), FPN paper eq. 1."""
        w = rois[..., 2] - rois[..., 0] + 1.0
        h = rois[..., 3] - rois[..., 1] + 1.0
        k = jnp.floor(4.0 + jnp.log2(jnp.sqrt(w * h) / 224.0 + 1e-8))
        return jnp.clip(k, 2.0, 5.0).astype(jnp.int32) - 2

    def _pool_levels(self, feats, rois, pooled: int):
        """Pool rois from their assigned pyramid level (static shapes: pool
        all 4 RoI levels, one-hot select).  feats: P2..P5 (B, H, W, C);
        rois: (B, R, 4) image coords → (B, R, P, P, C)."""
        lvl = self._assign_level(rois)  # (B, R)
        acc = None
        for li in range(4):
            scale = 1.0 / self._strides[li]
            p = jax.vmap(lambda f, r, s=scale: roi_align(
                f.astype(self._dtype), r, spatial_scale=s, pooled_size=pooled,
                sampling_ratio=self.cfg.tpu.ROI_SAMPLING_RATIO,
                mode=self.cfg.tpu.ROI_MODE))(feats[li], rois)
            sel = (lvl == li).astype(p.dtype)[..., None, None, None]
            acc = p * sel if acc is None else acc + p * sel
        return acc

    def _box_head(self, feats, rois):
        pooled = self._pool_levels(feats, rois, pooled=7)
        return self.rcnn_out(self.head_body(pooled))

    # ---- shared training pieces (used by end2end AND the stage graphs) -----

    def _rpn_losses(self, levels, im_info, gt_boxes, gt_valid, keys):
        """Anchor assignment + RPN losses over the concatenated level set
        (one assign per image across all levels — standard FPN training).
        Returns (total, aux)."""
        tr = self.cfg.TRAIN
        B = gt_boxes.shape[0]
        all_cls = jnp.concatenate([c for c, _, _ in levels], axis=1)
        all_bbox = jnp.concatenate([b for _, b, _ in levels], axis=1)
        all_anc = jnp.concatenate([a for _, _, a in levels], axis=0)
        assign = jax.vmap(
            lambda gtb, gtv, info, k: assign_anchor(
                all_anc, gtb, gtv, info[0], info[1], k,
                batch_size=tr.RPN_BATCH_SIZE, fg_fraction=tr.RPN_FG_FRACTION,
                pos_overlap=tr.RPN_POSITIVE_OVERLAP,
                neg_overlap=tr.RPN_NEGATIVE_OVERLAP,
                allowed_border=tr.RPN_ALLOWED_BORDER,
                clobber_positives=tr.RPN_CLOBBER_POSITIVES,
                iou_bf16=tr.RPN_ASSIGN_IOU_BF16,
                fused=self.cfg.tpu.ASSIGN_FUSED)
        )(gt_boxes, gt_valid, im_info, keys)
        rpn_cls_loss = L.softmax_ce_ignore(all_cls, assign["label"])
        rpn_bbox_loss = L.smooth_l1(all_bbox, assign["bbox_target"],
                                    assign["bbox_weight"], sigma=3.0,
                                    norm=float(tr.RPN_BATCH_SIZE) * B)
        aux = {"rpn_cls_loss": rpn_cls_loss, "rpn_bbox_loss": rpn_bbox_loss,
               "rpn_label": assign["label"],
               "rpn_pred": jnp.argmax(all_cls, axis=-1)}
        return rpn_cls_loss + rpn_bbox_loss, aux

    def _propose_train(self, levels, im_info):
        """Training-config proposals: per-level top-k + joint NMS (non-
        differentiable by the Proposal-op contract)."""
        tr = self.cfg.TRAIN
        level_scores = [jax.lax.stop_gradient(L.fg_prob(c))
                        for c, _, _ in levels]
        level_deltas = [jax.lax.stop_gradient(b) for _, b, _ in levels]
        anchors_l = [a for _, _, a in levels]
        rois, _, roi_valid = jax.vmap(
            lambda ls, ld, info: propose_fpn(
                list(ls), list(ld), anchors_l, info[0], info[1], info[2],
                pre_nms_top_n=tr.RPN_PRE_NMS_TOP_N,
                post_nms_top_n=tr.RPN_POST_NMS_TOP_N,
                nms_thresh=tr.RPN_NMS_THRESH, min_size=tr.RPN_MIN_SIZE,
                use_pallas=tr.CXX_PROPOSAL),
        )(tuple(level_scores), tuple(level_deltas), im_info)
        return rois, roi_valid

    def _rcnn_losses(self, feats, rois, roi_valid, gt_boxes, gt_classes,
                     gt_valid, keys):
        """RoI sampling (ProposalTarget contract) + box-head losses.
        Returns (total, aux, tgt)."""
        cfg = self.cfg
        tr = cfg.TRAIN
        B = gt_boxes.shape[0]
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
        cls_logits, bbox_out = self._box_head(feats, tgt["rois"])
        rcnn_cls_loss = L.softmax_ce_weighted(cls_logits, tgt["label"],
                                              tgt["label_weight"])
        rcnn_bbox_loss = L.smooth_l1(bbox_out, tgt["bbox_target"],
                                     tgt["bbox_weight"], sigma=1.0,
                                     norm=float(tr.BATCH_ROIS) * B)
        aux = {"rcnn_cls_loss": rcnn_cls_loss, "rcnn_bbox_loss": rcnn_bbox_loss,
               "rcnn_label": tgt["label"],
               "rcnn_pred": jnp.argmax(cls_logits, axis=-1),
               "rcnn_label_weight": tgt["label_weight"]}
        return rcnn_cls_loss + rcnn_bbox_loss, aux, tgt

    # ---- train graph -------------------------------------------------------

    def __call__(self, images, im_info, gt_boxes, gt_classes, gt_valid, key,
                 gt_masks: Optional[jnp.ndarray] = None):
        cfg = self.cfg
        B = images.shape[0]
        feats = self._pyramid(images)
        levels = self._rpn_over_levels(feats)
        keys = jax.random.split(key, (B, 2))

        rpn_total, rpn_aux = self._rpn_losses(levels, im_info, gt_boxes,
                                              gt_valid, keys[:, 0])
        rois, roi_valid = self._propose_train(levels, im_info)
        rcnn_total, rcnn_aux, tgt = self._rcnn_losses(
            feats, rois, roi_valid, gt_boxes, gt_classes, gt_valid, keys[:, 1])
        total = rpn_total + rcnn_total
        aux = {**rpn_aux, **rcnn_aux}

        if cfg.network.HAS_MASK and gt_masks is not None:
            pooled14 = self._pool_levels(feats, tgt["rois"], pooled=14)
            mask_logits = self.mask_head(pooled14)  # (B, R, 28, 28, K)
            m = self.cfg.TRAIN.MASK_SIZE
            targets = jax.vmap(
                lambda gm, gtb, r, gi: mask_targets_for_rois(
                    gm, gtb, r, gi, out_size=m)
            )(gt_masks, gt_boxes, tgt["rois"], tgt["gt_index"])
            # per-class logits: pick the sampled label's channel
            sel = jax.nn.one_hot(tgt["label"], cfg.NUM_CLASSES,
                                 dtype=mask_logits.dtype)
            logit = jnp.einsum("brhwk,brk->brhw", mask_logits, sel)
            w = tgt["is_fg"].astype(jnp.float32) * (tgt["label"] > 0)
            mask_loss = jax.vmap(L.mask_bce)(logit, targets, w).mean()
            total = total + mask_loss
            aux["mask_loss"] = mask_loss

        return total, aux

    # ---- test graph --------------------------------------------------------

    def predict(self, images, im_info):
        out, _ = self.predict_with_feats(images, im_info)
        return out

    def predict_with_feats(self, images, im_info):
        """predict + the pyramid features, so the mask branch can reuse them
        (eval runs mask chunks per batch without re-running the backbone)."""
        cfg = self.cfg
        te = cfg.TEST
        # the five stages carry their names into the device trace's op
        # metadata (fpn/neck holds the trunk too: _pyramid is one call)
        with jax.named_scope("fpn/neck"):
            feats = self._pyramid(images)
        with jax.named_scope("fpn/rpn"):
            levels = self._rpn_over_levels(feats)
            level_scores = [L.fg_prob(c) for c, _, _ in levels]
            level_deltas = [b for _, b, _ in levels]
            anchors_l = [a for _, _, a in levels]
        with jax.named_scope("fpn/propose"):
            rois, roi_scores, roi_valid = jax.vmap(
                lambda ls, ld, info: propose_fpn(
                    list(ls), list(ld), anchors_l, info[0], info[1], info[2],
                    pre_nms_top_n=te.RPN_PRE_NMS_TOP_N,
                    post_nms_top_n=te.RPN_POST_NMS_TOP_N,
                    nms_thresh=te.RPN_NMS_THRESH, min_size=te.RPN_MIN_SIZE,
                    use_pallas=te.CXX_PROPOSAL),
            )(tuple(level_scores), tuple(level_deltas), im_info)
        with jax.named_scope("fpn/pool"):
            pooled = self._pool_levels(feats, rois, pooled=7)
        with jax.named_scope("fpn/box_head"):
            cls_logits, bbox_deltas = self.rcnn_out(self.head_body(pooled))
            cls_prob = jax.nn.softmax(cls_logits, axis=-1)
        return (rois, roi_valid, cls_prob, bbox_deltas, roi_scores), feats

    def masks_from_feats(self, feats, boxes, labels):
        """Mask branch over precomputed pyramid features: (B, R, 4) boxes +
        (B, R) labels → (B, R, 28, 28) sigmoid probabilities."""
        with jax.named_scope("fpn/mask_pool"):
            pooled14 = self._pool_levels(feats, boxes, pooled=14)
        with jax.named_scope("fpn/mask_head"):
            mask_logits = self.mask_head(pooled14)
            sel = jax.nn.one_hot(labels, self.cfg.NUM_CLASSES,
                                 dtype=mask_logits.dtype)
            logit = jnp.einsum("brhwk,brk->brhw", mask_logits, sel)
            return jax.nn.sigmoid(logit)

    def predict_masks(self, images, im_info, boxes, labels):
        """Mask branch from raw images (standalone use; eval prefers
        predict_with_feats + masks_from_feats)."""
        del im_info
        return self.masks_from_feats(self._pyramid(images), boxes, labels)

    # ---- alternate-training stage graphs (classic pipeline on FPN) ---------

    def rpn_train(self, images, im_info, gt_boxes, gt_valid, key):
        """RPN-only training over the pyramid (alternate steps 1/4)."""
        B = images.shape[0]
        feats = self._pyramid(images)
        levels = self._rpn_over_levels(feats)
        return self._rpn_losses(levels, im_info, gt_boxes, gt_valid,
                                jax.random.split(key, B))

    def rcnn_train(self, images, im_info, rois, roi_valid, gt_boxes,
                   gt_classes, gt_valid, key):
        """Box-head training on supplied proposals (alternate steps 3/6).

        Mask configs must train end2end — the stage pipeline has no mask
        targets, and silently leaving the mask head at init would produce
        garbage masks at eval."""
        if self.cfg.network.HAS_MASK:
            raise NotImplementedError(
                "alternate training has no mask-target path; train mask "
                "configs end2end (train_end2end.py)")
        B = images.shape[0]
        feats = self._pyramid(images)
        total, aux, _ = self._rcnn_losses(
            feats, rois, roi_valid, gt_boxes, gt_classes, gt_valid,
            jax.random.split(key, B))
        return total, aux

    def predict_rpn(self, images, im_info):
        te = self.cfg.TEST
        feats = self._pyramid(images)
        levels = self._rpn_over_levels(feats)
        level_scores = [L.fg_prob(c) for c, _, _ in levels]
        level_deltas = [b for _, b, _ in levels]
        anchors_l = [a for _, _, a in levels]
        return jax.vmap(
            lambda ls, ld, info: propose_fpn(
                list(ls), list(ld), anchors_l, info[0], info[1], info[2],
                pre_nms_top_n=te.RPN_PRE_NMS_TOP_N,
                post_nms_top_n=te.RPN_POST_NMS_TOP_N,
                nms_thresh=te.RPN_NMS_THRESH, min_size=te.RPN_MIN_SIZE,
                use_pallas=te.CXX_PROPOSAL),
        )(tuple(level_scores), tuple(level_deltas), im_info)
