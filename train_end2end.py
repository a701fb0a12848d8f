#!/usr/bin/env python
"""End-to-end Faster R-CNN training driver.

Mirrors the reference's ``train_end2end.py`` argv surface and ``train_net``
flow: generate_config → imdb/roidb (+flip, filter) → AnchorLoader →
params (pretrained overlay + new heads at init) → fit (jitted DP step,
six metrics, Speedometer, epoch checkpoints with the bbox de-normalize
contract, --resume).

TPU specifics: ``--devices N`` picks the data-mesh size (the ``--gpus``
equivalent); ``--synthetic`` trains on generated data with zero files on
disk; ``--num-steps`` caps steps for smoke runs.  Multi-host (the
reference's unscripted ``KVStore('dist_sync')`` tier): run the same
command on every host with ``--dist-auto`` (TPU pod) or the
``--dist-coordinator/--dist-num-processes/--dist-process-id`` triple —
each process loads its slice of every global batch and XLA's collectives
do the cross-host gradient reduce (``parallel/distributed.py``).
"""

from __future__ import annotations

import argparse

import jax

from mx_rcnn_tpu.compile import setup_compile_cache
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.data import AnchorLoader
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.tools.common import (CappedLoader, add_common_args,
                                      check_dist_loader, config_from_args,
                                      get_imdb, get_train_roidb,
                                      init_or_load_params, replay_from_args,
                                      setup_parallel, start_observability,
                                      strip_device_prep_for_mesh)
from mx_rcnn_tpu.train import ResilienceOptions, fit


def parse_args(argv=None):
    parser = argparse.ArgumentParser(description="Train Faster R-CNN end2end")
    add_common_args(parser, train=True)
    parser.add_argument("--profile", default="",
                        help="write an XProf device trace of early steps here")
    # --steps-per-dispatch comes from add_common_args (shared with the
    # alternate-training stage tools since round 5)
    return parser.parse_args(argv)


def train_net(args):
    setup_compile_cache()
    # rendezvous before anything can touch the jax backend
    plan, pidx, pcount = setup_parallel(args)
    cfg = config_from_args(args, train=True)
    # --device-prep (and a tuned cell that selected it) is single-mesh
    # only: downgrade BEFORE the loader is built, or it would emit raw
    # uint8 batches the mesh path cannot prep
    cfg = strip_device_prep_for_mesh(cfg, plan)
    n_dev = plan.n_data if plan else 1
    batch_size = args.batch_images or n_dev * cfg.TRAIN.BATCH_IMAGES
    if plan and batch_size % n_dev:
        raise ValueError(f"batch_images {batch_size} not divisible by "
                         f"mesh size {n_dev}")

    imdb = get_imdb(args, cfg)
    roidb = get_train_roidb(imdb, cfg)
    # data flywheel (--replay-manifest): mix mined serving captures into
    # the epoch plan; the mix is drawn from the loader's plan RNG, so it
    # replays bit-identically under --auto-resume
    replay_roidb, replay_ratio = replay_from_args(args, cfg)
    loader = AnchorLoader(roidb, cfg, batch_size,
                          shuffle=cfg.TRAIN.SHUFFLE,
                          num_parts=pcount, part_index=pidx,
                          replay_roidb=replay_roidb,
                          replay_ratio=replay_ratio)
    check_dist_loader(plan, batch_size, pcount, pidx)
    if args.num_steps:
        loader = CappedLoader(loader, args.num_steps)
    logger.info("training on %d images, %d steps/epoch, batch %d over %d "
                "device(s)", len(roidb), loader.steps_per_epoch, batch_size,
                n_dev)

    model = build_model(cfg)
    params = init_or_load_params(args, cfg, model, batch_size)
    # live plane (inert without --obs-port): when it configures the sink,
    # fit reuses it (owns_tel=False) and the plane writes the summary
    obs = start_observability(args, "train_end2end", rank=pidx,
                              world=pcount,
                              run_meta={"network": args.network,
                                        "batch_size": batch_size})
    try:
        state = fit(cfg, model, params, loader,
                    begin_epoch=args.begin_epoch, end_epoch=args.end_epoch,
                    plan=plan, prefix=args.prefix, graph="end2end",
                    seed=getattr(args, "seed", 0),
                    frequent=args.frequent, resume=args.resume,
                    profile_dir=getattr(args, "profile", "") or None,
                    telemetry_dir=getattr(args, "telemetry_dir", "") or None,
                    steps_per_dispatch=getattr(args, "steps_per_dispatch", 1),
                    fixed_prefixes=cfg.network.FIXED_PARAMS,
                    resilience=ResilienceOptions.from_args(args))
    finally:
        obs.close()
    return state


if __name__ == "__main__":
    train_net(parse_args())
