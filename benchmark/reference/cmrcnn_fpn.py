"""Plain reference: Cascade Mask R-CNN on a ResNet Feature Pyramid Network,
inference forward.  Fills the ``reference`` row of ``benchmark/README.md``,
"A configuration", for ``r101-fpn-cascade-mask``.

Written from the published description — Cai & Vasconcelos, "Cascade R-CNN:
High Quality Object Detection and Instance Segmentation" (arXiv:1906.09756),
sections 3-4: a sequence of box heads, each trained at a higher IoU and
each applied to the boxes the one before it regressed — and from the
test-time rule of Detectron2's ``CascadeROIHeads``
(``configs/Misc/cascade_mask_rcnn_R_50_FPN_3x.yaml``, at trunk depth 101),
in straightforward ``jax.numpy`` / numpy, float32, ``precision=HIGHEST``.
No kernels, no batching, no cache.  It imports nothing of the program under
test and takes nothing the program made; the trunk, pyramid, RPN, proposal
layer, level map and RoIAlign are ``benchmark.reference.frcnn_fpn``'s, the
mask branch ``benchmark.reference.mrcnn_fpn``'s, the general arithmetic
``benchmark.reference.frcnn_c4``'s.

Per image, with b_1 the RPN's proposals (``frcnn_fpn.proposals``), for
t = 1, 2, 3: x_t = RoIAlign 7x7 on the level eq. 1 of the FPN paper
assigns to b_t; h_t = ReLU(FC-1024(ReLU(FC-1024(x_t)))) with stage t's own
weights; s_t = softmax(W^cls_t h_t) over the 81 classes; Delta_t = W^box_t
h_t, four numbers a RoI for every class; b_{t+1} = clip(decode(b_t,
Delta_t)), dw and dh held under log(1000 / 16) first, no box dropped.  The
answer is one box a RoI, b_4, for every class, scored by (s_1 + s_2 + s_3)
/ 3, each s_t taken on its own stage's input boxes; then the per-class NMS,
the cap, and the mask branch on the final detections.

Delta_t is the stage's **raw** delta: the division by the stage's weights
w_t (10, 10, 5, 5) / (20, 20, 10, 10) / (30, 30, 15, 15) is carried by the
weights themselves, as mx-rcnn's checkpoints carry it (the trained
parametrisation regresses w_t x the raw delta, and the fold at save
multiplies each stage's output layer by 1 / w_t): the configuration's
``assumed`` says so.

``precision``: "f32" (the reference proper) or "fp8" (every conv / matmul
input and weight rounded to float8_e4m3 under a per-tensor scale, float32
accumulation: the control of the ``correct`` comparison).

Departures from the sources, all stated in the configuration's ``assumed``
and followed here because the program makes them: ``frcnn_fpn``'s list
(one joint RPN NMS, "+1" widths, nearest top-down, P6 = P5[::2, ::2],
RoIAlign without the half-pixel shift) and ``mrcnn_fpn``'s (the paste, 81
mask channels).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import frcnn_fpn
from benchmark.reference.frcnn_c4 import (clip, decode_body, decode_boxes,
                                          dense, prepare)

# exp(dw) stays under 1000 / 16 (Detectron2's Box2BoxTransform scale_clamp)
DELTA_CLAMP = math.log(1000.0 / 16.0)


def stage_names(t: int):
    """Stage t's head body and output layer, as the parameter paths have
    them: stage 1 is the pyramid detector's own head."""
    return (("head_body", "rcnn_out") if t == 1
            else (f"head_body{t}", f"rcnn_out{t}"))


# --------------------------------------------------------------- the layers

def conv_layers(depth: str = "resnet101", num_classes: int = 81,
                num_anchors: int = 3, channels: int = 256,
                hidden: int = 1024, stages: int = 3):
    """Every conv / fc of the network as data, in ``frcnn_fpn.conv_layers``'s
    form: its trunk, neck and RPN; stage 1's head with a 4-wide box output;
    then each later stage's head (``part`` "stage_fc", once a RoI a
    stage).  The mask head is ``mrcnn_fpn.mask_layers``'.  The
    configuration's ``weights`` module draws from these lists and its
    ``flops`` module counts from them."""
    out = [layer for layer in frcnn_fpn.conv_layers(
        depth, num_classes, num_anchors, channels, hidden)
        if layer[-1] != "head_fc"]
    for t in range(1, stages + 1):
        body, head = stage_names(t)
        part = "head_fc" if t == 1 else "stage_fc"
        out += [(f"{body}/fc6", 0, 0, frcnn_fpn.POOLED ** 2 * channels,
                 hidden, 1, None, True, part),
                (f"{body}/fc7", 0, 0, hidden, hidden, 1, None, True, part),
                (f"{head}/cls_score", 0, 0, hidden, num_classes, 1, None,
                 True, part),
                (f"{head}/bbox_pred", 0, 0, hidden, 4, 1, None, True, part)]
    return out


# ------------------------------------------------------------- on the device

@functools.partial(jax.jit, static_argnames=("t", "precision"))
def box_head(p, crops, t, precision="f32"):
    """crops (R, 7, 7, C) -> stage t's (class probabilities (R, K), raw
    deltas (R, 4))."""
    body, head = stage_names(t)
    x = crops.reshape(crops.shape[0], -1)
    for name in (f"{body}/fc6", f"{body}/fc7"):
        x = jax.nn.relu(dense(x, p[f"{name}/kernel"], p[f"{name}/bias"],
                              precision))
    cls = dense(x, p[f"{head}/cls_score/kernel"],
                p[f"{head}/cls_score/bias"], precision)
    box = dense(x, p[f"{head}/bbox_pred/kernel"],
                p[f"{head}/bbox_pred/bias"], precision)
    return jax.nn.softmax(cls, axis=-1), box


# ----------------------------------------------------- host side, in numpy

def refine(boxes, deltas, eh, ew):
    """b_{t+1} = clip(decode(b_t, Delta_t)): "+1" widths, dw and dh held
    under ``DELTA_CLAMP``, clipped to the image's resized extent."""
    d = np.asarray(deltas, np.float32).copy()
    d[:, 2:] = np.minimum(d[:, 2:], DELTA_CLAMP)
    return clip(decode_boxes(np.asarray(boxes, np.float32), d), eh, ew)


def run_stages(p, feats, rois, eh, ew, net, precision="f32",
               roi_block: int = 100, order=None):
    """The box stages over the pyramid from the proposals ``rois`` (R, 4),
    in the resized frame.  ``order``: the stage heads in the order they run
    (default 1 .. ``net["cascade_stages"]``; a test and the control run
    others, to see the comparison catch them).
    -> {"boxes": [b_1 .. b_{n+1}], "probs": [s_1 .. s_n]}."""
    order = tuple(order or range(1, net["cascade_stages"] + 1))
    boxes, probs = [np.asarray(rois, np.float32)], []
    n = len(rois)
    pad = (-n) % roi_block
    for t in order:
        b = boxes[-1]
        crops = frcnn_fpn.pool_on_levels(feats, b, frcnn_fpn.assign_level(b),
                                         net["strides"], roi_block)
        crops = np.concatenate([crops, np.repeat(crops[:1], pad, 0)])
        pr, dl = [], []
        for i in range(0, len(crops), roi_block):
            a, d = box_head(p, jnp.asarray(crops[i:i + roi_block]), t=t,
                            precision=precision)
            pr.append(np.asarray(a))
            dl.append(np.asarray(d))
        probs.append(np.concatenate(pr)[:n])
        boxes.append(refine(b, np.concatenate(dl)[:n], eh, ew))
    return {"boxes": boxes, "probs": probs}


def detect(p, doc: dict, net: dict, precision: str = "f32",
           roi_block: int = 100, stages: dict | None = None,
           order=None) -> dict:
    """One request body -> what the reference stands by, in the document
    ``mrcnn_fpn.detect`` gives (so that ``mrcnn_fpn.masks`` reads it): the
    dense candidates ``prob`` (R, K) = the stages' averaged probabilities
    and ``boxes`` (R, 4K) = each RoI's final box b_4 in the original
    image's coordinates, the same for every class (each class's candidate
    is the RoI's one box); the request's float32 pyramid, resize factor,
    raw size and mask-head parameters; and for the comparison's two
    numbers on the stages, ``box_s2`` (R, 4) = b_2, stage 1's boxes once
    decoded, and ``prob_s1`` (R, K) = stage 1's probabilities alone.
    ``stages``, where given, receives every intermediate by name."""
    st = {} if stages is None else stages
    im = decode_body(doc)
    x, (eh, ew, s) = prepare(im, net["scale"], net["pixel_means"],
                             net["pixel_stds"], net["image_stride"])
    feats, heads = frcnn_fpn.pyramid_and_rpn(p, jnp.asarray(x[None]),
                                             depth=net["depth"],
                                             precision=precision)
    per_level = [(np.asarray(lg), np.asarray(dl),
                  frcnn_fpn.level_anchors(f.shape[1], f.shape[2], stride,
                                          net["anchor_scale"],
                                          net["anchor_ratios"]))
                 for f, (lg, dl), stride in zip(feats, heads, net["strides"])]
    rois, roi_scores = frcnn_fpn.proposals(per_level, eh, ew, s, net)
    run = run_stages(p, feats, rois, eh, ew, net, precision, roi_block, order)
    prob = sum(run["probs"]) / len(run["probs"])
    st.update(image=x, im_info=(eh, ew, s), feats=feats, rois=rois,
              roi_scores=roi_scores, **run)
    h, w = im.shape[:2]
    final = run["boxes"][-1] / s
    return {"prob": prob, "boxes": np.tile(final, (1, net["num_classes"])),
            "box": final, "box_s2": run["boxes"][1] / s,
            "prob_s1": run["probs"][0],
            "feats": [f[0] for f in feats[:4]], "scale": s,
            "hw": (int(h), int(w)),
            "params": {k: v for k, v in p.items()
                       if k.startswith("mask_head/")},
            "precision": precision}
