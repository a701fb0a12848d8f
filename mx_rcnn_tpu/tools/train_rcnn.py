"""Stage tool: Fast-RCNN training on cached proposals (reference
``rcnn/tools/train_rcnn.py`` — alternate-training steps 3 and 6): ROIIter
ships proposals; sampling happens in-graph (``FasterRCNN.rcnn_train``)."""

from __future__ import annotations

import argparse

from mx_rcnn_tpu.compile import setup_compile_cache
from mx_rcnn_tpu.data import ROIIter
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.tools.common import (CappedLoader, add_common_args,
                                      check_dist_loader, config_from_args,
                                      get_imdb, get_train_roidb,
                                      init_or_load_params, setup_parallel)
from mx_rcnn_tpu.train import ResilienceOptions, fit


def train_rcnn(args, cfg=None, params=None, roidb=None, frozen_shared=False):
    setup_compile_cache()
    plan, pidx, pcount = setup_parallel(args)
    cfg = cfg or config_from_args(args, train=True)
    n_dev = plan.n_data if plan else 1
    batch_size = (getattr(args, "batch_images", None)
                  or n_dev * cfg.TRAIN.BATCH_IMAGES)
    if plan and batch_size % n_dev:
        raise ValueError(f"batch_images {batch_size} not divisible by "
                         f"mesh size {n_dev}")
    if roidb is None:
        imdb = get_imdb(args, cfg)
        source = getattr(args, "proposals", "")
        base = None
        if source == "selective_search":
            # legacy Fast-RCNN input (reference selective_search_roidb)
            if not hasattr(imdb, "selective_search_roidb"):
                raise ValueError(
                    f"--proposals selective_search is a PascalVOC input; "
                    f"{type(imdb).__name__} has no selective-search data")
            base = imdb.selective_search_roidb()
        elif source:  # a test_rpn .pkl cache path (aligned with gt_roidb)
            from mx_rcnn_tpu.utils.load_data import load_proposals

            base = load_proposals(imdb.gt_roidb(), source)
        # attach-then-flip: get_train_roidb mirrors the proposals key
        roidb = get_train_roidb(imdb, cfg, roidb=base)
    if not any("proposals" in r for r in roidb):
        raise ValueError("roidb has no cached proposals — run test_rpn, or "
                         "pass --proposals {selective_search|<cache.pkl>}")
    loader = ROIIter(roidb, cfg, batch_size, shuffle=cfg.TRAIN.SHUFFLE,
                     num_parts=pcount, part_index=pidx)
    check_dist_loader(plan, batch_size, pcount, pidx)
    if getattr(args, "num_steps", 0):
        loader = CappedLoader(loader, args.num_steps)
    model = build_model(cfg)
    if params is None:
        params = init_or_load_params(args, cfg, model, batch_size)
    fixed = (cfg.network.FIXED_PARAMS_SHARED if frozen_shared
             else cfg.network.FIXED_PARAMS)
    logger.info("train_rcnn: %d images, frozen=%s", len(roidb), fixed)
    state = fit(cfg, model, params, loader,
                begin_epoch=args.begin_epoch, end_epoch=args.end_epoch,
                plan=plan, prefix=getattr(args, "prefix", None), graph="rcnn",
                seed=getattr(args, "seed", 0),
                frequent=args.frequent, fixed_prefixes=fixed,
                telemetry_dir=getattr(args, "telemetry_dir", "") or None,
                steps_per_dispatch=getattr(args, "steps_per_dispatch", 1),
                resilience=ResilienceOptions.from_args(args))
    return state


def parse_args():
    parser = argparse.ArgumentParser(description="Train Fast R-CNN on proposals")
    add_common_args(parser, train=True)
    parser.add_argument("--proposals", default="",
                        help="proposal source: 'selective_search' (loads "
                             "root_path/selective_search_data/*.mat, the "
                             "legacy Fast-RCNN input) or a test_rpn .pkl "
                             "cache path; default expects proposals already "
                             "in the roidb")
    return parser.parse_args()


if __name__ == "__main__":
    train_rcnn(parse_args())
