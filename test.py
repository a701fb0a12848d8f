#!/usr/bin/env python
"""Detection evaluation driver (reference ``test.py`` → ``test_rcnn``):
load checkpoint → TestLoader → pred_eval (per-class NMS, max_per_image) →
imdb.evaluate_detections (VOC mAP / COCO AP)."""

from __future__ import annotations

import argparse

from mx_rcnn_tpu.compile import setup_compile_cache
from mx_rcnn_tpu.data import TestLoader
from mx_rcnn_tpu.eval import Predictor, pred_eval
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.tools.common import (add_common_args, apply_program_cache,
                                      calibrate_from_args, config_from_args,
                                      get_imdb, load_eval_params, make_plan,
                                      start_observability)


def parse_args():
    parser = argparse.ArgumentParser(description="Test a Faster R-CNN network")
    add_common_args(parser, train=False)
    parser.add_argument("--batch_images", type=int, default=0,
                        help="GLOBAL images per eval step (like train's "
                             "flag; must divide by the mesh's data "
                             "dimension).  Default: 1 per data-parallel "
                             "chip.")
    parser.add_argument("--dets_cache", default="",
                        help="pickle all_boxes here for tools/reeval.py "
                             "(the reference's detections.pkl)")
    parser.add_argument("--eval-inflight", type=int, default=None,
                        help="overlapped-eval dispatch window (default "
                             "cfg.tpu.EVAL_INFLIGHT=2); 0 forces the "
                             "serial reference loop")
    parser.add_argument("--eval-host-workers", type=int, default=None,
                        help="host post-process thread-pool width "
                             "(default cfg.tpu.EVAL_HOST_WORKERS=2)")
    parser.add_argument("--prefetch", type=int, default=None,
                        help="TestLoader prefetch depth override "
                             "(default cfg.tpu.PREFETCH)")
    parser.add_argument("--device-postprocess", action="store_true",
                        help="fuse box decode + per-class NMS into the "
                             "forward program and read back only "
                             "max_per_image detections per image (opt-in: "
                             "exact score ties at the cap may resolve "
                             "differently from host NMS)")
    return parser.parse_args()


def test_rcnn(args):
    setup_compile_cache()
    cfg = config_from_args(args, train=False)
    if args.device_postprocess and cfg.network.ROI_CASCADE_STAGES > 1:
        raise SystemExit(
            f"--device-postprocess cannot run the RoI cascade of "
            f"--network {args.network} (Cascade R-CNN): its fused program "
            f"decodes one box head's per-class deltas; drop the flag (the "
            f"default path runs the stages)")
    if args.device_postprocess and cfg.network.HAS_MASK \
            and cfg.TEST.MASK_PASTE == "native":
        # compact readbacks end to end: the same flag that fuses decode+NMS
        # moves mask paste onto the device (ops/mask_paste.py) so mask
        # responses ship packed bitplanes instead of (R, 28, 28) floats.
        # An explicit --cfg TEST__MASK_PASTE override still wins.
        import dataclasses

        cfg = cfg.replace(TEST=dataclasses.replace(cfg.TEST,
                                                   MASK_PASTE="device"))
    apply_program_cache(args)  # before the Predictor builds its registry
    imdb = get_imdb(args, cfg, test=True)
    roidb = imdb.gt_roidb()
    model = build_model(cfg)
    params = load_eval_params(args, cfg, model)
    # data-parallel eval when >1 device: params replicate, batch rows shard
    # over the mesh.  --batch_images is GLOBAL, matching train's flag
    # semantics (train_end2end.py uses it directly as the step batch);
    # defaulting it to n_data keeps the common single-flag invocation at
    # one image per data-parallel chip.
    plan = make_plan(args)
    n_data = plan.n_data if plan else 1
    bs = args.batch_images or n_data
    if bs % n_data:
        raise ValueError(
            f"--batch_images {bs} must divide by the mesh's data dimension "
            f"{n_data} (the flag is GLOBAL images per step, like train)")
    # --calibrate-shard (int8-activation only): scales from the FLOAT
    # params, persisted to the program cache before the variant cast
    act_scales = calibrate_from_args(args, cfg, model, params)
    predictor = Predictor(model, params, cfg, plan=plan,
                          dtype=args.infer_dtype, act_scales=act_scales)
    # eval is single-process (Predictor enforces it), so rank 0 / world 1
    # and the summary always belongs to this process; the plane owns the
    # sink lifecycle (and the /metrics endpoint when --obs-port is set)
    obs = start_observability(args, "test",
                              run_meta={"network": args.network,
                                        "batch_size": bs},
                              configure_telemetry=True)
    try:
        # --device-prep: the loader ships staged raw uint8 + sidecars and
        # the Predictor preps on device in its batch_put hook (mesh plans
        # raise at Predictor construction — host path only there)
        loader = TestLoader(roidb, cfg, batch_size=bs,
                            prefetch=args.prefetch,
                            device_prep=getattr(args, "device_prep", False))
        stats = pred_eval(predictor, loader, imdb, thresh=args.thresh,
                          vis=args.vis, with_masks=cfg.network.HAS_MASK,
                          det_cache=args.dets_cache or None,
                          inflight=args.eval_inflight,
                          host_workers=args.eval_host_workers,
                          device_postprocess=args.device_postprocess)
    finally:
        obs.close()

    def flat(d, prefix=""):
        out = {}
        for k, v in d.items():
            if isinstance(v, dict):
                out.update(flat(v, prefix + k + "/"))
            elif isinstance(v, (int, float)):
                out[prefix + k] = round(float(v), 4)
        return out

    logger.info("evaluation done: %s", flat(stats))
    return stats


if __name__ == "__main__":
    test_rcnn(parse_args())
