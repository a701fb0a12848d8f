"""Stage tool: RPN proposal generation/eval (reference
``rcnn/tools/test_rpn.py`` — alternate-training steps 2 and 5): run the
RPN-only test graph over the roidb and cache per-image proposals."""

from __future__ import annotations

import argparse
import os

from mx_rcnn_tpu.compile import setup_compile_cache
from mx_rcnn_tpu.data import TestLoader
from mx_rcnn_tpu.eval import Predictor, generate_proposals
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.tools.common import (add_common_args, config_from_args,
                                      get_imdb, load_eval_params)


def test_rpn(args, cfg=None, params=None, imdb=None, roidb=None):
    setup_compile_cache()
    cfg = cfg or config_from_args(args, train=False)
    if imdb is None:
        imdb = get_imdb(args, cfg)
    if roidb is None:
        roidb = imdb.gt_roidb()
    model = build_model(cfg)
    if params is None:
        params = load_eval_params(args, cfg, model)
    predictor = Predictor(model, params, cfg)
    loader = TestLoader(roidb, cfg, batch_size=1)
    cache = os.path.join(imdb.cache_path, f"{imdb.name}_rpn_proposals.pkl")
    roidb = generate_proposals(predictor, loader, imdb, roidb,
                               cache_path=cache)
    n = sum(len(r.get("proposals", ())) for r in roidb)
    logger.info("test_rpn: %d proposals over %d images", n, len(roidb))
    return roidb


def parse_args():
    parser = argparse.ArgumentParser(description="Generate RPN proposals")
    add_common_args(parser, train=False)
    return parser.parse_args()


if __name__ == "__main__":
    test_rpn(parse_args())
