"""Worker for tests/test_warmstart.py — ONE server boot over a
shared persistent program cache.

Invoked as ``python tests/aot_worker.py <cache_base>`` (mp_worker.py
pattern: env before the jax import, parseable stdout lines).  Builds the
tiny synthetic-weight serve stack from tests/test_serve.py, runs warmup
through a real ServeEngine, and prints one line the test parses:

    WARM programs=P aot_hit=H aot_miss=M warmup_programs=W wall=S

Run twice over the same ``cache_base`` this is the whole AOT warm-start
claim: the first process misses every program (cold compile, markers +
XLA executables written), the second reports ``aot_hit ==
warmup_programs`` and zero misses — every warmup "compile" was a disk
load from the cache dir the first process populated.
"""

from __future__ import annotations

import os
import sys
import time


def main(cache_base: str) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["MXR_PROGRAM_CACHE"] = cache_base
    import dataclasses

    import jax

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.eval import Predictor
    from mx_rcnn_tpu.models import build_model, init_params
    from mx_rcnn_tpu.serve import ServeEngine, ServeOptions, warmup
    from mx_rcnn_tpu.train.checkpoint import denormalize_for_save

    # tests/test_serve.py's tiny_cfg — MUST be identical between the two
    # boots (the config digest is part of every program key)
    cfg = generate_config(
        "resnet50", "PascalVOC",
        TEST__RPN_PRE_NMS_TOP_N=300, TEST__RPN_POST_NMS_TOP_N=32,
    )
    net = dataclasses.replace(cfg.network, ANCHOR_SCALES=(2, 4))
    tpu = dataclasses.replace(cfg.tpu, SCALES=((96, 128),), MAX_GT=8)
    cfg = cfg.replace(network=net, tpu=tpu)

    model = build_model(cfg)
    params = denormalize_for_save(
        init_params(model, cfg, jax.random.PRNGKey(0), 1, (96, 128)), cfg)
    pred = Predictor(model, params, cfg)
    assert pred.registry.owns_cache, "MXR_PROGRAM_CACHE should be honored"

    t0 = time.perf_counter()
    engine = ServeEngine(pred, cfg, ServeOptions(
        batch_size=1, max_delay_ms=1.0, max_queue=8)).start()
    try:
        warmup(engine)
    finally:
        engine.stop()
    wall = time.perf_counter() - t0

    c = pred.registry.counters
    print(f"WARM programs={c['programs']} aot_hit={c['aot_hit']} "
          f"aot_miss={c['aot_miss']} "
          f"warmup_programs={engine.counters['warmup_programs']} "
          f"wall={wall:.3f}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
