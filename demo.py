#!/usr/bin/env python
"""Single-image demo (reference ``demo.py``): load image → resize to the
scale bucket → forward → bbox decode + per-class NMS → print/draw boxes."""

from __future__ import annotations

import argparse

import numpy as np

from mx_rcnn_tpu.compile import setup_compile_cache
from mx_rcnn_tpu.data.image import get_image, resize_to_bucket, transform_image
from mx_rcnn_tpu.eval import Predictor, im_detect
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.native import nms
from mx_rcnn_tpu.tools.common import (add_common_args, config_from_args,
                                      load_eval_params)


def parse_args():
    parser = argparse.ArgumentParser(description="Demo: detect one image")
    add_common_args(parser, train=False)
    parser.add_argument("--image", required=True)
    parser.add_argument("--out", default="",
                        help="write visualization to this path")
    parser.set_defaults(thresh=0.5)  # visualization default (reference demo)
    return parser.parse_args()


def demo_net(args):
    setup_compile_cache()
    cfg = config_from_args(args, train=False)
    model = build_model(cfg)
    params = load_eval_params(args, cfg, model)
    predictor = Predictor(model, params, cfg)

    im = get_image(args.image)
    orig = im.copy()
    im = transform_image(im, cfg.network.PIXEL_MEANS, cfg.network.PIXEL_STDS)
    stride = max(cfg.network.IMAGE_STRIDE, cfg.network.RPN_FEAT_STRIDE)
    padded, s, (eh, ew) = resize_to_bucket(im, cfg.tpu.SCALES[0], stride)
    batch = dict(images=padded[None],
                 im_info=np.asarray([[eh, ew, s]], np.float32),
                 batch_valid=np.asarray([True]))
    (scores, boxes, valid), = im_detect(predictor, batch)

    from mx_rcnn_tpu.data.pascal_voc import VOC_CLASSES

    if cfg.NUM_CLASSES == len(VOC_CLASSES):
        classes = list(VOC_CLASSES)
    else:
        classes = [f"class{i}" for i in range(cfg.NUM_CLASSES)]

    all_dets = []
    v = np.asarray(valid, bool)
    for k in range(1, cfg.NUM_CLASSES):
        sel = (scores[:, k] > args.thresh) & v
        dets = np.hstack([boxes[sel, 4 * k:4 * (k + 1)],
                          scores[sel, k][:, None]]).astype(np.float32)
        keep = nms(dets, cfg.TEST.NMS)
        for d in dets[keep]:
            all_dets.append((classes[k], d))
            logger.info("%s: %.3f at [%.1f, %.1f, %.1f, %.1f]",
                        classes[k], d[4], *d[:4])

    if args.out:
        import cv2

        from mx_rcnn_tpu.eval.tester import draw_detections

        img = cv2.cvtColor(orig, cv2.COLOR_RGB2BGR)
        draw_detections(img, all_dets)
        cv2.imwrite(args.out, img)
        logger.info("wrote %s (%d detections)", args.out, len(all_dets))
    return all_dets


if __name__ == "__main__":
    demo_net(parse_args())
