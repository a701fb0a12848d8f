"""Subprocess TCP fabric-member entry for the e2e cross-host chaos
tests (NOT a test module — no ``test_`` prefix).

The localhost-TCP twin of ``tests/replica_worker.py``: the REAL member
main loop (``serve_replica``: TCP HTTP, warmup→ready, ``/admin/reload``
hot swap, ``--join`` self-registration, ``MXR_FAULT_NET_*`` injectors)
over the shape-faithful :class:`FakeServePredictor` — no model weights,
no XLA forward — so ``tests/test_fabric.py`` can drive a real
ReplicaPool + FabricRouter over real processes and real sockets
(kill -9, TCP resets, blackholes) in seconds.
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mx_rcnn_tpu.serve import ServeEngine, ServeOptions, serve_replica  # noqa: E402
from tests.replica_worker import FakeServePredictor, load_params  # noqa: E402
from tests.test_serve import tiny_cfg  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--replica-index", type=int, default=0,
                    dest="replica_index")
    ap.add_argument("--params-file", default="", dest="params_file")
    ap.add_argument("--serve-batch", type=int, default=2, dest="serve_batch")
    ap.add_argument("--delay-s", type=float, default=0.0, dest="delay_s")
    ap.add_argument("--join", default="")
    ap.add_argument("--advertise", default="")
    # fleet-flywheel capture (ISSUE 17): members share one capture dir,
    # distinguished by --capture-member in shard/manifest names
    ap.add_argument("--capture-dir", default="", dest="capture_dir")
    ap.add_argument("--capture-member", default=None,
                    dest="capture_member")
    ap.add_argument("--capture-sample", type=int, default=1,
                    dest="capture_sample")
    ap.add_argument("--capture-shard-records", type=int, default=4,
                    dest="capture_shard_records")
    args = ap.parse_args(argv)

    cfg = tiny_cfg()
    params = {"scale": np.float32(1.0)}
    if args.params_file:
        params = load_params({"prefix": args.params_file}, cfg)
    pred = FakeServePredictor(cfg, params, delay_s=args.delay_s)
    engine = ServeEngine(pred, cfg, ServeOptions(
        batch_size=args.serve_batch, max_delay_ms=1.0,
        max_queue=32))
    if args.capture_dir:
        from mx_rcnn_tpu.flywheel import CaptureOptions, RequestCapture
        engine.capture = RequestCapture(CaptureOptions(
            capture_dir=args.capture_dir,
            sample_every=args.capture_sample,
            shard_records=args.capture_shard_records,
            member=args.capture_member))
    engine.start()
    serve_replica(engine, cfg, port=args.port, index=args.replica_index,
                  predictor=pred, load_params_fn=load_params,
                  join=args.join or None,
                  advertise=args.advertise or None)


if __name__ == "__main__":
    main()
