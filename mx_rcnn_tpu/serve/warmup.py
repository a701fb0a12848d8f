"""Startup warmup: ensure every (bucket, batch) program is registered
and ready — compiled from disk (AOT warm start) or from XLA (cold).

XLA compiles the forward on first dispatch of each input shape — tens of
seconds for the real backbones.  Without warmup the first user request of
each orientation pays that compile inside its latency budget (and usually
blows its deadline).  Warmup pushes one full batch of dummy pixels per
bucket through the REAL engine path — same queue, same padding, same
post-process, and on a mask network the same second dispatch (the mask
program runs at its one static shape whatever the number of records, so
a zero image warms it and it is counted) — so every program the steady
state can dispatch is ready
before the frontend accepts traffic, and the engine's recompile counter
(the program registry's first-dispatch bookkeeping) proves it: after
warmup, ``counters["recompiles"] == counters["warmup_programs"]`` must
hold for the life of the process (asserted by
``tests/test_serve.py``).

With a persistent program cache (``MXR_PROGRAM_CACHE``), warmup is where
the AOT win lands: a second boot over a warm cache dir reports
``compile/aot_hit == warmup_programs`` and zero ``aot_miss`` — every
"compile" is a disk load, and the logged warmup wall time collapses
(asserted by ``script/aot_smoke.sh`` and ``tests/test_warmstart.py``).
"""

from __future__ import annotations

import numpy as np

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.compile.registry import xla_counters
from mx_rcnn_tpu.logger import logger


def warmup(engine) -> int:
    """Register + ready every (bucket, batch) program through a STARTED
    engine.

    Submits ``batch_size`` dummy images per bucket — landscape and
    portrait, which a square scale routes to the one bucket both share, so
    such a network warms its programs once — (full batches → immediate
    flush, no delay wait) and blocks until served.  Returns the
    number of programs first-dispatched (each either an XLA compile or a
    persistent-cache load); stamps it into
    ``engine.counters["warmup_programs"]`` and the
    ``serve/warmup_programs`` telemetry counter, the warmup wall time
    into the ``serve/warmup_compile_s`` gauge, and — when the engine's
    predictor carries a :class:`~mx_rcnn_tpu.compile.ProgramRegistry` —
    logs the AOT hit/miss split for the warmed programs."""
    # pool-mode engines have no thread of their own: the ModelPool
    # dispatcher flushes them, so warmup only needs SOME dispatcher live
    assert engine._thread is not None or engine._external, \
        "start() the engine before warmup"
    short, long_ = engine._scale
    reg = getattr(engine, "registry", None)
    before = engine.counters["recompiles"]
    aot_before = (dict(reg.counters) if reg is not None else {})
    xla_before = xla_counters()
    with telemetry.stage("setup/warmup") as whole:
        # landscape, portrait: one dummy shape a distinct bucket
        shapes = {engine.bucket_key(h, w): (h, w)
                  for h, w in ((short, long_), (long_, short))}
        for h, w in shapes.values():
            dummy = np.zeros((h, w, 3), np.uint8)
            futs = [engine.submit(dummy, deadline_ms=0)  # never expire
                    for _ in range(engine.opts.batch_size)]
            for f in futs:
                f.result(timeout=600.0)
        # a future resolves before the dispatcher has booked its batch:
        # wait for that too, so that the counters read here, and by
        # whoever asks once warm-up has returned, hold every warm-up batch
        with engine._cond:
            while engine._inflight:
                engine._cond.wait(timeout=0.05)
    dt = engine.setup["warmup_s"] = whole.seconds
    compiled = engine.counters["recompiles"] - before
    engine.counters["warmup_programs"] += compiled
    # warmup completion IS readiness: /readyz flips to 200 here, so a
    # supervisor never routes traffic into a replica still compiling
    engine.mark_ready()
    tel = telemetry.get()
    tel.counter("serve/warmup_programs", compiled)
    tel.gauge("serve/warmup_compile_s", dt)
    if reg is not None:
        hits = reg.counters["aot_hit"] - aot_before.get("aot_hit", 0)
        misses = reg.counters["aot_miss"] - aot_before.get("aot_miss", 0)
        # the markers' forecast beside what XLA did meanwhile, for every
        # program of the process (the one-op ones too)
        xla = {k: v - xla_before[k] for k, v in xla_counters().items()}
        logger.info("serve warmup: %d program(s) ready in %.1fs — "
                    "forecast %d AOT cache hit(s), %d compile(s); XLA "
                    "built %d program(s) in %.1fs, %d of them loaded from "
                    "the persistent cache, %d missed it (batch=%d, "
                    "scale=%s, dtype=%s)", compiled, dt, hits, misses,
                    xla["xla_compiles"], xla["xla_compile_s"],
                    xla["persistent_cache_hits"],
                    xla["persistent_cache_misses"],
                    engine.opts.batch_size, engine._scale,
                    getattr(engine, "_dtype", "float32"))
    else:
        logger.info("serve warmup: %d program(s) compiled in %.1fs "
                    "(batch=%d, scale=%s)", compiled, dt,
                    engine.opts.batch_size, engine._scale)
    return compiled
