#!/usr/bin/env python
"""4-step alternate Faster R-CNN training (reference ``train_alternate.py``):

1. train RPN from pretrained
2. generate proposals with the trained RPN
3. train Fast-RCNN on the cached proposals
4. train RPN round 2 — shared conv frozen (FIXED_PARAMS_SHARED)
5. proposals round 2
6. train Fast-RCNN round 2 — shared conv frozen
7. combine_model → single deployment checkpoint

Runs in-process (the reference shells out per stage); each stage reuses the
previous stage's params exactly like the reference's load_param chain.

``--tuned-pipeline`` (tools/common.config_from_args) applies the persisted
input-pipeline cell from ``python -m mx_rcnn_tpu.train.pipeline
--auto-tune`` before any stage runs; ``stage_args`` copies of ``args``
carry the tuned ``steps_per_dispatch`` into every fit-based stage, and
the tuned loader knobs (workers/prefetch/device-prep) ride the shared
``cfg``.  Proposal stages (2/5) go through TestLoader, which always uses
the host preprocessing path regardless of ``--device-prep``.
"""

from __future__ import annotations

import argparse

import jax

from mx_rcnn_tpu.compile import setup_compile_cache
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.tools.common import (add_common_args, config_from_args,
                                      get_imdb, get_train_roidb,
                                      init_or_load_params,
                                      start_observability)
from mx_rcnn_tpu.tools.test_rpn import test_rpn
from mx_rcnn_tpu.tools.train_rcnn import train_rcnn
from mx_rcnn_tpu.tools.train_rpn import train_rpn
from mx_rcnn_tpu.train.checkpoint import CheckpointManager
from mx_rcnn_tpu.utils import combine_model


def parse_args():
    parser = argparse.ArgumentParser(description="Train Faster R-CNN alternately")
    add_common_args(parser, train=True)
    parser.add_argument("--rpn_epochs", type=int, default=None,
                        help="epochs per RPN stage (default: end_epoch)")
    parser.add_argument("--rcnn_epochs", type=int, default=None,
                        help="epochs per RCNN stage (default: end_epoch)")
    return parser.parse_args()


def alternate_train(args):
    setup_compile_cache()
    if (getattr(args, "dist_auto", False)
            or getattr(args, "dist_coordinator", None) is not None
            or getattr(args, "dist_num_processes", None) is not None
            or getattr(args, "dist_process_id", None) is not None):
        raise NotImplementedError(
            "alternate training is single-process: stages 2/5 dump "
            "proposals through the eval path, which has no multi-host "
            "mode.  Run the train stages multi-host individually "
            "(tools/train_rpn.py, tools/train_rcnn.py --dist-*) or use "
            "train_end2end.py --dist-*")
    cfg = config_from_args(args, train=True)
    if cfg.network.HAS_MASK:
        raise NotImplementedError(
            "alternate training has no mask-target path; train mask configs "
            "end2end (train_end2end.py)")
    imdb = get_imdb(args, cfg)
    roidb = get_train_roidb(imdb, cfg)
    model = build_model(cfg)
    params = init_or_load_params(args, cfg, model, 1)
    rpn_ep = args.rpn_epochs or args.end_epoch
    rcnn_ep = args.rcnn_epochs or args.end_epoch

    def stage_args(end_epoch):
        a = argparse.Namespace(**vars(args))
        a.begin_epoch, a.end_epoch, a.prefix = 0, end_epoch, None
        return a

    # one obs plane across every stage (inert without --obs-port) — the
    # per-stage fits reuse the plane's sink instead of opening their own,
    # so a scrape mid-run sees the whole alternate sequence accumulate
    obs = start_observability(args, "train_alternate",
                              run_meta={"network": args.network})
    try:
        logger.info("=== stage 1: train RPN ===")
        s1 = train_rpn(stage_args(rpn_ep), cfg=cfg, params=params,
                       roidb=roidb)
        logger.info("=== stage 2: generate proposals ===")
        roidb = test_rpn(args, cfg=cfg, params=jax.device_get(s1.params),
                         imdb=imdb, roidb=roidb)
        logger.info("=== stage 3: train RCNN on proposals ===")
        s3 = train_rcnn(stage_args(rcnn_ep), cfg=cfg, params=params,
                        roidb=roidb)
        logger.info("=== stage 4: train RPN round 2 (shared conv frozen) ===")
        s4 = train_rpn(stage_args(rpn_ep), cfg=cfg,
                       params=jax.device_get(s3.params), roidb=roidb,
                       frozen_shared=True)
        logger.info("=== stage 5: proposals round 2 ===")
        roidb = test_rpn(args, cfg=cfg, params=jax.device_get(s4.params),
                         imdb=imdb, roidb=roidb)
        logger.info("=== stage 6: train RCNN round 2 (shared conv frozen) ===")
        s6 = train_rcnn(stage_args(rcnn_ep), cfg=cfg,
                        params=jax.device_get(s4.params), roidb=roidb,
                        frozen_shared=True)
        logger.info("=== stage 7: combine_model ===")
        final = combine_model(jax.device_get(s4.params),
                              jax.device_get(s6.params))
        mgr = CheckpointManager(args.prefix)
        mgr.save_epoch(args.end_epoch, final, cfg, step=0)
        logger.info("combined checkpoint saved to %s", args.prefix)
    finally:
        obs.close()
    return final


if __name__ == "__main__":
    alternate_train(parse_args())
