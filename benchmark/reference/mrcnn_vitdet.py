"""Plain reference: Mask R-CNN on a plain ViT backbone with the simple
feature pyramid (ViTDet), inference forward.  Fills the ``reference`` row of
``benchmark/README.md``, "A configuration", for ``vitdet-b-mask``.

Written from the published description — Li, Mao, Girshick, He 2022,
"Exploring Plain Vision Transformer Backbones for Object Detection"
(arXiv:2203.16527), sections 3 and 4.1, and the public baseline's model
definition (Detectron2 ``projects/ViTDet/configs/common/models/
mask_rcnn_vitdet.py``) — in straightforward ``jax.numpy`` / numpy, float32,
``precision=HIGHEST``, one image at a time, every score matrix
materialised (805 MB a global block at 4096 tokens and 12 heads).  No
kernels, no batching, no cache.  It imports nothing of the program under
test and takes nothing the program made; what is general to the detectors
here (a conv, a matmul, the float8 rounding, the request body's decode and
resize, box arithmetic, greedy NMS, anchors a level, the level map, RoIAlign,
the paste) comes from ``benchmark.reference.frcnn_c4``, ``frcnn_fpn`` and
``mrcnn_fpn``.

The six groups of equations (ISSUE 34; x is the G x G x C token grid):

1. patch embedding: a P x P convolution of stride P with bias, then
   ``x += pos_embed``;
2. block i: ``x += Attn_i(LN(x)); x += W2 gelu(W1 LN(x) + b1) + b2``; LN
   over the channels, eps 1e-6, affine; the exact (erf) GELU; no final norm;
3. a head's attention over S x S keys: ``softmax(s q k^T + Bh + Bw)``,
   s = d^-1/2, ``Bh[(y,x),(y',x')] = q[y,x] . Rh[y - y' + S - 1]`` from the
   unscaled q, and the same along x;
4. windowed blocks: after LN the grid is zero-padded at the bottom and
   right to whole windows, attention runs inside each window (padding
   tokens are keys like any other), the grid is cropped back; global blocks
   attend over all G x G tokens;
5. simple feature pyramid from the last block's map F: P2 = deconv 2x2/2
   (C -> C/2), LN, GELU, deconv 2x2/2 (C/2 -> C/4); P3 = deconv 2x2/2
   (C -> C/2); P4 = F; P5 = max-pool 2x2/2 of F; each then a 1x1 conv and a
   3x3 conv to 256 without bias, an LN over the channels after each; P6 =
   P5[::2, ::2], for the RPN only;
6. heads: RPN = two 3x3 convs with ReLU, then objectness and deltas, shared
   by P2..P6; box head = 4 x (3x3 conv without bias, LN, ReLU) on the 7x7
   crop, flatten (rows, columns, channels), FC + ReLU, the class and box
   outputs; mask head = 4 x (3x3 conv without bias, LN, ReLU) on 14x14, the
   2x2 deconv + ReLU, the 1x1 to the mask channels.

Proposals, level assignment, RoIAlign, decode and paste are
``r101-fpn-mask``'s, with its departures (the configuration's ``assumed``).

``precision``: "f32" (the reference proper) or "fp8" (every conv / matmul
input and weight, the attention's q, k, v and probabilities among them,
rounded to float8_e4m3 under a per-tensor scale, float32 accumulation: the
control of the ``correct`` comparison).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import frcnn_fpn
from benchmark.reference.frcnn_c4 import (HIGHEST, _prep, clip, conv,
                                          decode_body, decode_boxes, dense,
                                          prepare)
from benchmark.reference.mrcnn_fpn import ROI_BLOCK, paste, pool as pool14

LN_EPS = 1e-6
POOLED = frcnn_fpn.POOLED


# --------------------------------------------------------------- the layers

def sizes(net: dict) -> dict:
    """The sizes everything below is counted from: the token grid, the
    padded grid of a windowed block, the five levels' sides."""
    v = net["vit"]
    g = net["scale"][0] // v["patch"]
    win = v["window"]
    gp = -(-g // win) * win
    side = {2: 4 * g, 3: 2 * g, 4: g, 5: g // 2}
    side[6] = (side[5] + 1) // 2
    return {"grid": g, "padded": gp, "windows": (gp // win) ** 2,
            "side": side}


def layers(net: dict):
    """Every conv / matmul of the network as data: (path, kind, kh, kw, cin,
    cout, has_bias, part, positions).  ``kind``: "conv", "deconv" (2x2
    stride 2: every output pixel hears one tap) or "fc".  ``positions``: at
    how many output positions (an image, or a RoI for the heads) the layer's
    kh x kw x cin x cout multiply-accumulates run (a deconv: cin x cout).
    The configuration's ``weights`` module draws from this list and its
    ``flops`` module counts from it."""
    v, sz = net["vit"], sizes(net)
    c, g, ch = v["width"], sz["grid"], net["fpn_channels"]
    out = [("backbone/patch_embed", "conv", v["patch"], v["patch"], 3, c,
            True, "patch_embed", g * g)]
    for i in range(v["depth"]):
        glob = i in v["global_blocks"]
        tokens = g * g if glob else sz["padded"] ** 2
        part = "block_global" if glob else "block_window"
        b = f"backbone/block{i}"
        out += [(f"{b}/attn/qkv", "fc", 1, 1, c, 3 * c, True, part, tokens),
                (f"{b}/attn/proj", "fc", 1, 1, c, c, True, part, tokens),
                (f"{b}/fc1", "fc", 1, 1, c, v["mlp_ratio"] * c, True, part,
                 g * g),
                (f"{b}/fc2", "fc", 1, 1, v["mlp_ratio"] * c, c, True, part,
                 g * g)]
    s = sz["side"]
    out += [("neck/p2_deconv1", "deconv", 2, 2, c, c // 2, True, "sfp",
             s[3] ** 2),
            ("neck/p2_deconv2", "deconv", 2, 2, c // 2, c // 4, True, "sfp",
             s[2] ** 2),
            ("neck/p3_deconv", "deconv", 2, 2, c, c // 2, True, "sfp",
             s[3] ** 2)]
    for lvl, cin in ((2, c // 4), (3, c // 2), (4, c), (5, c)):
        out += [(f"neck/lateral{lvl}", "conv", 1, 1, cin, ch, False, "sfp",
                 s[lvl] ** 2),
                (f"neck/post{lvl}", "conv", 3, 3, ch, ch, False, "sfp",
                 s[lvl] ** 2)]
    every = sum(n * n for n in s.values())
    a = net["num_anchors"]
    for i in range(1, net["rpn_convs"] + 1):
        out.append((rpn_conv_name(i), "conv", 3, 3, ch, ch, True, "rpn",
                    every))
    out += [("rpn/rpn_cls_score", "conv", 1, 1, ch, 2 * a, True, "rpn",
             every),
            ("rpn/rpn_bbox_pred", "conv", 1, 1, ch, 4 * a, True, "rpn",
             every)]
    for i in range(1, net["head_convs"] + 1):
        out.append((f"head_body/conv{i}", "conv", 3, 3, ch, ch, False,
                    "box_head", POOLED * POOLED))
    h, k = net["head_hidden"], net["num_classes"]
    out += [("head_body/fc6", "fc", 1, 1, POOLED * POOLED * ch, h, True,
             "box_head", 1),
            ("rcnn_out/cls_score", "fc", 1, 1, h, k, True, "box_head", 1),
            ("rcnn_out/bbox_pred", "fc", 1, 1, h, 4 * k, True, "box_head", 1)]
    m, mc = net["mask_pooled"], net["mask_channels"]
    for i in range(1, net["mask_convs"] + 1):
        out.append((f"mask_head/mask_conv{i}", "conv", 3, 3, mc, mc, False,
                    "mask_head", m * m))
    out += [("mask_head/mask_deconv", "deconv", 2, 2, mc, mc, True,
             "mask_head", 4 * m * m),
            ("mask_head/mask_out", "conv", 1, 1, mc, k, True, "mask_head",
             4 * m * m)]
    return out


def rpn_conv_name(i: int) -> str:
    return "rpn/rpn_conv_3x3" if i == 1 else f"rpn/rpn_conv_3x3_{i}"


def norms(net: dict):
    """[(path, width)] of every LayerNorm (leaves ``scale`` and ``bias``)."""
    v = net["vit"]
    c, ch = v["width"], net["fpn_channels"]
    out = [(f"backbone/block{i}/norm{j}", c) for i in range(v["depth"])
           for j in (1, 2)]
    out.append(("neck/p2_norm", c // 2))
    out += [(f"neck/{kind}{lvl}_norm", ch) for lvl in (2, 3, 4, 5)
            for kind in ("lateral", "post")]
    out += [(f"head_body/conv{i}_norm", ch)
            for i in range(1, net["head_convs"] + 1)]
    out += [(f"mask_head/mask_conv{i}_norm", net["mask_channels"])
            for i in range(1, net["mask_convs"] + 1)]
    return out


def position_leaves(net: dict):
    """[(path, shape)]: the absolute position vectors and every block's two
    relative tables (2S - 1, d), S the side of what the block attends over."""
    v, sz = net["vit"], sizes(net)
    d = v["width"] // v["heads"]
    out = [("backbone/pos_embed", (1, sz["grid"], sz["grid"], v["width"]))]
    for i in range(v["depth"]):
        s = sz["grid"] if i in v["global_blocks"] else v["window"]
        out += [(f"backbone/block{i}/attn/rel_pos_{ax}", (2 * s - 1, d))
                for ax in "hw"]
    return out


# ------------------------------------------------------------- on the device

def layer_norm(x, p, name):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + LN_EPS) * p[f"{name}/scale"]
            + p[f"{name}/bias"])


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.float32(np.sqrt(2.0))))


def _fc(x, p, name, precision):
    return dense(x, p[f"{name}/kernel"], p[f"{name}/bias"], precision)


def _mm(eq, a, b, precision):
    a, b = _prep(a, b, precision)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def attention(p, name, x, heads, precision):
    """x (n, S, S, C): n independent grids of S x S tokens -> the same
    shape.  Equation 3, scores materialised."""
    n, s, _, c = x.shape
    d = c // heads
    qkv = _fc(x.reshape(n, s * s, c), p, f"{name}/qkv", precision)
    q, k, v = qkv.reshape(n, s * s, 3, heads, d).transpose(2, 0, 3, 1, 4)
    idx = np.arange(s)[:, None] - np.arange(s)[None, :] + s - 1   # [y, y']
    qg = q.reshape(n, heads, s, s, d)
    bh = _mm("nhyxd,yjd->nhyxj", qg, p[f"{name}/rel_pos_h"][idx], precision)
    bw = _mm("nhyxd,xjd->nhyxj", qg, p[f"{name}/rel_pos_w"][idx], precision)
    scores = _mm("nhqd,nhkd->nhqk", q, k, precision) * np.float32(d ** -0.5)
    scores = (scores.reshape(n, heads, s, s, s, s)
              + bh[..., :, None] + bw[..., None, :]).reshape(
                  n, heads, s * s, s * s)
    prob = jax.nn.softmax(scores, axis=-1)
    o = _mm("nhqk,nhkd->nhqd", prob, v, precision)
    o = o.transpose(0, 2, 1, 3).reshape(n, s * s, c)
    return _fc(o, p, f"{name}/proj", precision).reshape(n, s, s, c)


def block(p, i, x, v, precision):
    """Equations 2 and 4 on one image's grid x (1, G, G, C)."""
    name = f"backbone/block{i}"
    _, g, _, c = x.shape
    h = layer_norm(x, p, f"{name}/norm1")
    if i in v["global_blocks"]:
        h = attention(p, f"{name}/attn", h, v["heads"], precision)
    else:
        w = v["window"]
        gp = -(-g // w) * w
        h = jnp.pad(h, ((0, 0), (0, gp - g), (0, gp - g), (0, 0)))
        n = gp // w
        h = h.reshape(n, w, n, w, c).transpose(0, 2, 1, 3, 4).reshape(
            n * n, w, w, c)
        h = attention(p, f"{name}/attn", h, v["heads"], precision)
        h = h.reshape(n, n, w, w, c).transpose(0, 2, 1, 3, 4).reshape(
            1, gp, gp, c)[:, :g, :g]
    x = x + h
    h = gelu(_fc(layer_norm(x, p, f"{name}/norm2"), p, f"{name}/fc1",
                 precision))
    return x + _fc(h, p, f"{name}/fc2", precision)


def trunk(p, image, v, precision):
    """image (1, H, W, 3) -> the last block's map (1, G, G, C)."""
    ps = v["patch"]
    _, h, w, _ = image.shape
    patches = image.reshape(h // ps, ps, w // ps, ps, 3).transpose(
        0, 2, 1, 3, 4).reshape(-1, ps * ps * 3)
    k = p["backbone/patch_embed/kernel"]
    x = dense(patches, k.reshape(-1, k.shape[-1]),
              p["backbone/patch_embed/bias"], precision)
    x = x.reshape(1, h // ps, w // ps, -1) + p["backbone/pos_embed"]
    for i in range(v["depth"]):
        x = block(p, i, x, v, precision)
    return x


def deconv2(x, p, name, precision):
    """2x2 stride-2 deconv with bias: every input cell writes its own 2x2
    output block; the kernel stored as ``lax.conv_transpose`` reads it (tap
    (a, b) writes output pixel (2i + 1 - a, 2j + 1 - b))."""
    k = p[f"{name}/kernel"][::-1, ::-1]                  # (a, b, cin, cout)
    n, h, w, c = x.shape
    o = k.shape[-1]
    y = dense(x.reshape(-1, c), k.transpose(2, 0, 1, 3).reshape(c, 4 * o),
              0.0, precision)
    y = y.reshape(n, h, w, 2, 2, o).transpose(0, 1, 3, 2, 4, 5).reshape(
        n, 2 * h, 2 * w, o)
    return y + p[f"{name}/bias"]


def pyramid(p, f, precision):
    """Equation 5: F (1, G, G, C) -> P2..P6."""
    x2 = gelu(layer_norm(deconv2(f, p, "neck/p2_deconv1", precision), p,
                         "neck/p2_norm"))
    _, g, _, c = f.shape
    levels = {2: deconv2(x2, p, "neck/p2_deconv2", precision),
              3: deconv2(f, p, "neck/p3_deconv", precision),
              4: f,
              5: f.reshape(1, g // 2, 2, g // 2, 2, c).max((2, 4))}
    out = []
    for lvl, x in levels.items():
        x = layer_norm(conv(x, p[f"neck/lateral{lvl}/kernel"], 1, precision),
                       p, f"neck/lateral{lvl}_norm")
        out.append(layer_norm(conv(x, p[f"neck/post{lvl}/kernel"], 1,
                                   precision), p, f"neck/post{lvl}_norm"))
    return (*out, out[-1][:, ::2, ::2])


def rpn(p, feat, convs, precision):
    """One level's map (1, h, w, C) -> (logits (h*w*A, 2), deltas (h*w*A,
    4)), anchor index (y * w + x) * A + a."""
    h = feat
    for i in range(1, convs + 1):
        n = rpn_conv_name(i)
        h = jax.nn.relu(conv(h, p[f"{n}/kernel"], 1, precision)
                        + p[f"{n}/bias"])
    out = lambda n: conv(h, p[f"rpn/{n}/kernel"], 1, precision) \
        + p[f"rpn/{n}/bias"]  # noqa: E731
    return out("rpn_cls_score").reshape(-1, 2), \
        out("rpn_bbox_pred").reshape(-1, 4)


class _Static(dict):
    """A dict that may be a static argument of ``jax.jit``."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def static_vit(net: dict) -> _Static:
    """``net["vit"]`` (the trunk's sizes) as a static argument."""
    return _Static({k: tuple(v) if isinstance(v, list) else v
                    for k, v in net["vit"].items()})


@functools.partial(jax.jit, static_argnames=("v", "rpn_convs", "precision"))
def pyramid_and_rpn(p, image, v, rpn_convs, precision="f32"):
    """image (1, H, W, 3) normalised float32 -> (P2..P6, [(logits, deltas)
    a level])."""
    feats = pyramid(p, trunk(p, image, v, precision), precision)
    return feats, [rpn(p, f, rpn_convs, precision) for f in feats]


def _conv_ln_relu(x, p, name, precision):
    return jax.nn.relu(layer_norm(conv(x, p[f"{name}/kernel"], 1, precision),
                                  p, f"{name}_norm"))


@functools.partial(jax.jit, static_argnames=("convs", "precision"))
def box_head(p, crops, convs=4, precision="f32"):
    """crops (R, 7, 7, C) -> (class probabilities (R, K), deltas (R, 4K))."""
    x = crops
    for i in range(1, convs + 1):
        x = _conv_ln_relu(x, p, f"head_body/conv{i}", precision)
    x = jax.nn.relu(_fc(x.reshape(x.shape[0], -1), p, "head_body/fc6",
                        precision))
    return (jax.nn.softmax(_fc(x, p, "rcnn_out/cls_score", precision), -1),
            _fc(x, p, "rcnn_out/bbox_pred", precision))


@functools.partial(jax.jit, static_argnames=("convs", "precision"))
def mask_head(p, crops, convs=4, precision="f32"):
    """crops (R, 14, 14, C) -> per-class sigmoid maps (R, 28, 28, K)."""
    x = crops
    for i in range(1, convs + 1):
        x = _conv_ln_relu(x, p, f"mask_head/mask_conv{i}", precision)
    x = jax.nn.relu(deconv2(x, p, "mask_head/mask_deconv", precision))
    return jax.nn.sigmoid(conv(x, p["mask_head/mask_out/kernel"], 1,
                               precision) + p["mask_head/mask_out/bias"])


# ----------------------------------------------------- host side, in numpy

def detect(p, doc: dict, net: dict, precision: str = "f32",
           roi_block: int = 100, stages: dict | None = None) -> dict:
    """One request body -> what the reference stands by: the dense box
    candidates ``prob`` (R, K) and ``boxes`` (R, 4K) in the original image's
    coordinates, and for the mask branch the request's own pyramid ``feats``
    (P2..P5, float32, on the device), its resize factor ``scale``, its raw
    ``hw`` and the mask head's parameters.  ``stages``, where given,
    receives every intermediate by name."""
    im = decode_body(doc)
    x, (eh, ew, s) = prepare(im, net["scale"], net["pixel_means"],
                             net["pixel_stds"], net["image_stride"])
    feats, heads = pyramid_and_rpn(p, jnp.asarray(x[None]),
                                   v=static_vit(net),
                                   rpn_convs=net["rpn_convs"],
                                   precision=precision)
    strides = net["strides"]
    per_level = [(np.asarray(lg), np.asarray(dl), frcnn_fpn.level_anchors(
                      f.shape[1], f.shape[2], st, net["anchor_scale"],
                      net["anchor_ratios"]))
                 for f, (lg, dl), st in zip(feats, heads, strides)]
    rois, roi_scores = frcnn_fpn.proposals(per_level, eh, ew, s, net)
    levels = frcnn_fpn.assign_level(rois)
    crops = frcnn_fpn.pool_on_levels(feats, rois, levels, strides, roi_block)
    n = len(rois)
    crops_p = np.concatenate([crops, np.repeat(crops[:1], (-n) % roi_block,
                                               0)])
    probs, dls = [], []
    for i in range(0, len(crops_p), roi_block):
        pr, dl = box_head(p, jnp.asarray(crops_p[i:i + roi_block]),
                          convs=net["head_convs"], precision=precision)
        probs.append(np.asarray(pr))
        dls.append(np.asarray(dl))
    prob = np.concatenate(probs)[:n]
    dl = np.concatenate(dls)[:n]
    if stages is not None:
        stages.update(image=x, im_info=(eh, ew, s), feats=feats,
                      per_level=per_level, rois=rois, roi_scores=roi_scores,
                      levels=levels, crops=crops, prob=prob, deltas=dl)
    h, w = im.shape[:2]
    return {"prob": prob, "boxes": clip(decode_boxes(rois, dl), eh, ew) / s,
            "feats": [f[0] for f in feats[:4]], "scale": s,
            "hw": (int(h), int(w)),
            "params": {k: val for k, val in p.items()
                       if k.startswith("mask_head/")},
            "precision": precision}


def mask_probs(dense_doc: dict, boxes: np.ndarray, labels, net: dict):
    """The branch up to its 28x28 maps: original-frame boxes (n, 4) and
    classes (n,) -> (n, M, M) probabilities of each box's own class, each
    box pooled on the one level eq. 1 assigns to it."""
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
            crops = pool14(dense_doc["feats"][li], jnp.asarray(scaled[pad]),
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
