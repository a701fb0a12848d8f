"""Subprocess replica entry for the e2e multi-replica chaos tests (NOT a
test module — no ``test_`` prefix).

Runs the REAL replica main loop (``serve_replica``: Unix-socket HTTP,
warmup→ready, ``/admin/reload`` hot swap, ``MXR_FAULT_REPLICA_*``
injectors) over the shape-faithful :class:`FakeServePredictor` — no
model weights, no XLA forward — so ``tests/test_replica.py`` can drive a
real supervisor + router over real processes (kill -9, respawn, rolling
reload) in seconds.

Hot-reload contract: ``--params-file`` points at a JSON dict of floats;
a reload target's ``prefix`` names such a file, and ``predict`` scales
its class scores by ``params["scale"]`` — so a swapped generation is
observable in responses and a NaN ``scale`` fails the canary probe.
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mx_rcnn_tpu.serve import ServeEngine, ServeOptions, serve_replica  # noqa: E402
from tests.test_serve import FakePredictor, tiny_cfg  # noqa: E402


class FakeServePredictor(FakePredictor):
    """FakePredictor + the hot-reload surface (``params`` /
    ``update_params``): scores scale with ``params["scale"]`` so weight
    swaps show up in outputs and NaN weights poison the canary."""

    def __init__(self, cfg, params, delay_s=0.0):
        super().__init__(cfg, delay_s=delay_s)
        self.params = params

    def update_params(self, params):
        self.params = params

    def predict(self, images, im_info):
        rois, valid, scores, deltas, extra = super().predict(images, im_info)
        s = np.float32(self.params.get("scale", 1.0))
        return rois, valid, scores * s, deltas * s, extra


def load_params(target, cfg):
    """Reload-target loader: ``target["prefix"]`` is a JSON params file."""
    with open(target["prefix"]) as f:
        doc = json.load(f)
    return {k: np.float32(v) for k, v in doc.items()}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--unix-socket", required=True, dest="unix_socket")
    ap.add_argument("--replica-index", type=int, default=0,
                    dest="replica_index")
    ap.add_argument("--params-file", default="", dest="params_file")
    ap.add_argument("--serve-batch", type=int, default=2, dest="serve_batch")
    ap.add_argument("--delay-s", type=float, default=0.0, dest="delay_s")
    args = ap.parse_args(argv)

    cfg = tiny_cfg()
    params = {"scale": np.float32(1.0)}
    if args.params_file:
        params = load_params({"prefix": args.params_file}, cfg)
    pred = FakeServePredictor(cfg, params, delay_s=args.delay_s)
    engine = ServeEngine(pred, cfg, ServeOptions(
        batch_size=args.serve_batch, max_delay_ms=1.0,
        max_queue=32)).start()
    serve_replica(engine, cfg, args.unix_socket, index=args.replica_index,
                  predictor=pred, load_params_fn=load_params)


if __name__ == "__main__":
    main()
