"""Stage tool: RPN-only training (reference ``rcnn/tools/train_rpn.py`` —
alternate-training steps 1 and 4).  Same loader as end2end; the graph is
``FasterRCNN.rpn_train`` (backbone + RPN heads + RPN losses only)."""

from __future__ import annotations

import argparse

from mx_rcnn_tpu.compile import setup_compile_cache
from mx_rcnn_tpu.data import AnchorLoader
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.tools.common import (CappedLoader, add_common_args,
                                      check_dist_loader, config_from_args,
                                      get_imdb, get_train_roidb,
                                      init_or_load_params, setup_parallel)
from mx_rcnn_tpu.train import ResilienceOptions, fit


def train_rpn(args, cfg=None, params=None, roidb=None, frozen_shared=False):
    """Callable both as a CLI stage and from train_alternate (which passes
    params of the previous stage and frozen_shared=True for round 2)."""
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
        roidb = get_train_roidb(imdb, cfg)
    loader = AnchorLoader(roidb, cfg, batch_size, shuffle=cfg.TRAIN.SHUFFLE,
                          num_parts=pcount, part_index=pidx)
    check_dist_loader(plan, batch_size, pcount, pidx)
    if getattr(args, "num_steps", 0):
        loader = CappedLoader(loader, args.num_steps)
    model = build_model(cfg)
    if params is None:
        params = init_or_load_params(args, cfg, model, batch_size)
    fixed = (cfg.network.FIXED_PARAMS_SHARED if frozen_shared
             else cfg.network.FIXED_PARAMS)
    logger.info("train_rpn: %d images, frozen=%s", len(roidb), fixed)
    state = fit(cfg, model, params, loader,
                begin_epoch=args.begin_epoch, end_epoch=args.end_epoch,
                plan=plan, prefix=getattr(args, "prefix", None), graph="rpn",
                seed=getattr(args, "seed", 0),
                frequent=args.frequent, fixed_prefixes=fixed,
                telemetry_dir=getattr(args, "telemetry_dir", "") or None,
                steps_per_dispatch=getattr(args, "steps_per_dispatch", 1),
                resilience=ResilienceOptions.from_args(args))
    return state


def parse_args():
    parser = argparse.ArgumentParser(description="Train RPN")
    add_common_args(parser, train=True)
    return parser.parse_args()


if __name__ == "__main__":
    train_rpn(parse_args())
