"""Benchmark: throughput of the flagship config on the attached TPU chip.

Default (what the driver runs): steady-state imgs/sec/chip of the jitted
end-to-end train step (ResNet-101 Faster R-CNN, 608×1024 bucket — the
BASELINE.json headline metric's throughput half; the accuracy half needs
COCO on disk), printed as exactly ONE JSON line:
  {"metric": "train_imgs_per_sec_per_chip", "value": N, "unit": "imgs/sec",
   "vs_baseline": R}

``vs_baseline`` is the METHOD-CONSISTENT ratio against
``BENCH_BASELINE.json`` (round 5 onward): chain-method runs divide by its
``value_chain`` (the round-4 clean-window chain measurement), staged runs
(``--legacy-dispatch``) by ``value`` (the round-1 v5-lite staged
measurement — BASELINE.md's "first measured baseline of our own"; the
reference repo's 8×V100 table was unrecoverable, see SURVEY §0).  The
emitted ``baseline_method`` field names the denominator's method.
Timing (round 4 onward) uses a ONE-dispatch
``lax.fori_loop`` step chain at two lengths, differenced so the dispatch +
readback fence cancels exactly (`bench_train_chain`) — the async-dispatch
chain it replaces read 23.7–65.9 imgs/s from one hour to the next for a
program whose device step was a stable 12.20 ms (rounds 3-4, a record since
deleted — a claim to check); `--legacy-dispatch` keeps the old method for
comparison.

Extra modes (manual, for BASELINE.md's scaling/honesty tables — each also
prints one JSON line):
  python bench.py --batch 4              # chain train step at B=4
  python bench.py --mode loader --loader-workers 4   # HOST pipeline
      standalone: real AnchorLoader over a synthetic roidb (cv2 resize,
      normalize, host s2d, batch assembly) with NO device step and NO
      transfer — pure host-pipeline imgs/sec, the number --loader-workers
      must scale.  method: "host_pipeline", never comparable to device
      rows; the _w{N} metric suffix keys worker counts apart.
  python bench.py --mode train-loader    # loader-INCLUSIVE train: real
      AnchorLoader over a synthetic roidb (cv2 resize, host s2d, prefetch
      thread with on-thread device transfer — all in the measured loop;
      the Speedometer-equivalent number)
  python bench.py --mode infer --batch 4 # chain inference (round 5;
      --legacy-dispatch selects the staged method in BOTH train and
      infer modes; infer output carries a "method" field so ledger rows
      are never cross-method-compared silently)
  python bench.py --mode infer-loader    # TestLoader + im_detect loop incl.
      per-image host decode/readback (the test.py loop without class NMS)
  python bench.py --mode serve --batch 4 # steady-state imgs/sec through the
      REAL ServeEngine (mx_rcnn_tpu/serve): mixed-size raw uint8 requests,
      caller-thread resize, bucket routing, dynamic batching, full
      post-process — everything but HTTP framing.  The gap between this
      and --mode infer is the serving tax (prep + batching + NMS); the
      output's "method" field says "engine" so ledger rows are never
      compared against forward-only numbers silently.
  python bench.py --mode pipeline --auto-tune   # input-pipeline tuner:
      sweep the (k steps/dispatch × loader workers × prefetch [×
      --device-prep]) matrix through the real train hot loop
      (mx_rcnn_tpu/train/pipeline.py), per-cell imgs/s + loader_wait/
      dispatch/fetch_stall/assembly_wait breakdown; --auto-tune persists
      the winner next to the program cache so train_end2end.py
      --tuned-pipeline boots into it.  method: "pipeline"
      (loader-inclusive), its own baseline key ("value_pipeline").
  python bench.py --mode eval            # whole pred_eval loop, three
      variants one row apart: serial (inflight=0), pipelined (the
      overlapped evaluator, the headline) and pipelined +
      --device-postprocess (fused decode+NMS, shrunk readback).  The
      "eval" sub-dict carries all three rates + speedup_vs_serial,
      which scripts/perf_gate.py scores against an absolute 1.0 floor.
      method: "pred_eval", its own baseline key ("value_eval").
  --workers-list/--prefetch-list on --mode loader / train-loader sweep
      the standalone cells in ONE invocation (headline = best, every
      cell in the JSON's "cells" array, metric suffixed _sweep).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
BASELINE_FILE = os.path.join(REPO, "BENCH_BASELINE.json")

# process-start reference for --mode serve's cold_start_s (bench.py is
# the entry script, so import time ≈ process start); the AOT warm-start
# win is exactly the drop in this number between a cold and a warm
# MXR_PROGRAM_CACHE run
_PROC_T0 = time.perf_counter()

H, W = 608, 1024
WARMUP = 5
STEPS = 30
# one-dispatch chain lengths (bench_train_chain); the difference n2-n1 is
# what gets timed, the fixed dispatch+fence cost cancels in the subtraction.
# SIZING MATTERS (first-version bug, r4_tpu_session7.log): with only 30
# steps of difference (~0.4 s device) a ±0.1 s+ dispatch-lag variance
# dominated, and taking the BEST of 3 pairs selected favorable
# noise — classic read 113 imgs/s against a 12.35 ms/step device truth
# (chain program profiled by scripts/profile_chain.py; max-of-noisy-
# differences is upward-biased).  160 steps of difference (~2 s device
# classic) bounds the lag noise to a few percent, and the median kills
# the selection bias.
CHAIN_N1, CHAIN_N2 = 40, 200


CFG_OVERRIDES: dict = {}  # set from --cfg (PATH=VALUE, common.py syntax)


def make_cfg(network: str = "resnet101"):
    from mx_rcnn_tpu.config import generate_config

    cfg = generate_config(network, "PascalVOC", **CFG_OVERRIDES)
    return cfg.replace(network=dataclasses.replace(
        cfg.network, PIXEL_STDS=(127.0, 127.0, 127.0)))


def synthetic_batch(cfg, batch):
    rng = np.random.RandomState(0)
    g = cfg.tpu.MAX_GT
    gtb = np.zeros((batch, g, 4), np.float32)
    gtv = np.zeros((batch, g), bool)
    gtc = np.zeros((batch, g), np.int32)
    for b in range(batch):
        for j in range(6):
            x1, y1 = rng.randint(0, W - 200), rng.randint(0, H - 200)
            gtb[b, j] = (x1, y1, x1 + rng.randint(60, 199),
                         y1 + rng.randint(60, 199))
            gtc[b, j] = rng.randint(1, 21)
            gtv[b, j] = True
    images = rng.randn(batch, H, W, 3).astype(np.float32)
    if cfg.network.HOST_S2D:  # ship images like the production loader does
        from mx_rcnn_tpu.data.image import space_to_depth2

        images = np.stack([space_to_depth2(im) for im in images])
    out = dict(
        images=images,
        im_info=np.tile(np.asarray([[H, W, 1.0]], np.float32), (batch, 1)),
        gt_boxes=gtb, gt_classes=gtc, gt_valid=gtv,
    )
    if cfg.network.HAS_MASK:
        from mx_rcnn_tpu.data.mask import GT_MASK_SIZE

        out["gt_masks"] = np.ones((batch, g, GT_MASK_SIZE, GT_MASK_SIZE),
                                  np.float32)
    return out


def build(batch: int = 1, network: str = "resnet101", donate: bool = True):
    from mx_rcnn_tpu.models import build_model, init_params
    from mx_rcnn_tpu.train import create_train_state, make_train_step

    cfg = make_cfg(network)
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0), batch, (H, W))
    state, tx, mask = create_train_state(cfg, params, steps_per_epoch=1000)
    step = make_train_step(model, tx, trainable_mask=mask, donate=donate)
    return state, step, synthetic_batch(cfg, batch), cfg


def make_chain_fn(step, dbatch, key=None):
    """The ONE definition of the n-step fori_loop chain program (shared
    by `bench_train_chain` and `scripts/profile_chain.py`, whose whole
    purpose is to profile the program the bench times — a drifted copy
    would silently validate a different program).  Per-iteration
    key-derived batch perturbation (sub-pixel gt jitter + epsilon image
    noise) poisons every LICM opportunity downstream; see
    `bench_train_chain` for the measured story."""
    from functools import partial

    if key is None:
        key = jax.random.PRNGKey(0)

    @partial(jax.jit, static_argnames=("n",), donate_argnums=(0,))
    def chain(st, n):
        def body(i, s):
            k = jax.random.fold_in(key, i)
            b = dict(dbatch)
            b["images"] = dbatch["images"] + jax.random.uniform(
                k, (), dtype=dbatch["images"].dtype, maxval=1e-3)
            b["gt_boxes"] = dbatch["gt_boxes"] + jax.random.uniform(
                jax.random.fold_in(k, 1), (), dtype=dbatch["gt_boxes"].dtype,
                maxval=0.9)
            return step(s, b, jax.random.fold_in(k, 2))[0]

        return jax.lax.fori_loop(0, n, body, st)

    return chain


def _differenced_rate(run, batch: int, fallback):
    """Shared timing protocol of the chain benches (train + infer): time
    the warmed ``run(n)`` at both CHAIN lengths in 3 pairs, skip pairs a
    window hiccup inverted, and difference so the dispatch + readback
    fence cancels exactly:

        imgs/s = (n2 - n1) * batch / (t(n2) - t(n1))

    Median of 3 valid pairs; LOWER-middle when pairs were skipped — with
    2 samples the upper-middle is max-of-noise, the exact selection bias
    the round-4 rewrite exists to kill (see CHAIN_N note).  ``fallback``
    runs the staged method when every pair inverts (pathological
    window).  ``run(n)`` must block on a real readback before returning.
    """
    n1, n2 = CHAIN_N1, CHAIN_N2
    rates = []
    for _ in range(3):
        ts = {}
        for n in (n1, n2):
            t0 = time.time()
            run(n)
            ts[n] = time.time() - t0
        if ts[n2] > ts[n1]:
            rates.append((n2 - n1) * batch / (ts[n2] - ts[n1]))
    if not rates:
        return fallback()
    return sorted(rates)[(len(rates) - 1) // 2]


def bench_train_chain(batch: int, network: str = "resnet101"):
    """One-dispatch chained-step timing — the headline method since round 4.

    The legacy method (``bench_train_staged``, kept behind
    ``--legacy-dispatch``) dispatches N async step calls and syncs once.
    That measures the host's dispatch rate along with the step: whenever
    the host falls behind, the device starves BETWEEN steps.  In rounds
    3-4 the same program read 23.7–65.9 imgs/s by this method while its
    xplane device step was a stable 12.20 ms every time (a record since
    deleted — a claim to check on a locally attached chip).  A wall
    metric whose spread is 3x the quantity it measures is noise.

    Here the whole chain is ONE program: ``lax.fori_loop`` over the train
    step (same jitted step function, traced inline; fresh fold_in key per
    iteration).  The staged batch is PERTURBED with key-derived noise
    every iteration (sub-pixel gt jitter + epsilon image noise) so that
    no data-dependent computation is loop-invariant.  This matters: a
    constant batch let XLA hoist per-batch work — the FPN chain ran
    3.9 ms/step faster than its own per-dispatch device profile because
    the 155k-anchor assign-IoU (constant gt) moved out of the loop, and
    even a 2-batch alternation left the gap (XLA computes both variants
    once and indexes).  Real training recomputes that work per fresh
    batch; the noise forces the loop to as well (r4_tpu_session7.log —
    validated: per-step time in-loop == per-dispatch device profile).
    Transfer overlap for real loaders is separately proven by the
    round-4 loader trace.  Two chain lengths are timed and differenced,
    so the single dispatch + readback fence cancels EXACTLY:

        imgs/s = (n2 - n1) * batch / (t(n2) - t(n1))
    """
    state, step, hbatch, _ = build(batch, network, donate=False)
    chain = make_chain_fn(step, jax.device_put(hbatch))

    n1, n2 = CHAIN_N1, CHAIN_N2
    s0 = int(jax.device_get(state.step))
    box = [state]

    def run(n):
        box[0] = chain(box[0], n)
        return int(jax.device_get(box[0].step))  # readback = fence

    for n in (n1, n2):  # compile + warm both lengths
        s1 = run(n)
    assert s1 - s0 == n1 + n2, f"chain ran {s1 - s0} steps, not {n1 + n2}"
    return _differenced_rate(run, batch,
                             lambda: bench_train_staged(batch, network))


def bench_train_staged(batch: int, network: str = "resnet101"):
    state, step, hbatch, _ = build(batch, network)
    # stage the (constant) batch in HBM once: measuring per-step host->device
    # shipping would benchmark the transfer, not the training step (real
    # training hides it behind the prefetcher's async device_put)
    dbatch = jax.device_put(hbatch)
    for i in range(WARMUP):
        state, m = step(state, dbatch, jax.random.PRNGKey(i))
    jax.block_until_ready(m)
    _ = float(jax.device_get(m["total_loss"]))  # full round-trip fence

    best = None
    for _ in range(4):   # host timing is noisy; best-of-4 chains
        t0 = time.time()
        for i in range(STEPS):
            state, m = step(state, dbatch, jax.random.PRNGKey(i))
        _ = float(jax.device_get(m["total_loss"]))  # fence via real readback
        dt = (time.time() - t0) / STEPS
        best = max(best or 0.0, batch / dt)
    return best


def _synthetic_roidb(n=48):
    from mx_rcnn_tpu.data.synthetic import SyntheticDataset

    return SyntheticDataset(num_images=n, height=600, width=800).gt_roidb()


def bench_train_loader(batch: int, network: str = "resnet101",
                       workers: int = 0, prefetch=None):
    """Loader-inclusive: cv2-free synthetic pixels, but the full production
    path otherwise — resize to bucket, host s2d, target padding, prefetch
    thread, host→device transfer ON the prefetch thread (the round-3
    double-buffering ``put`` hook, same as ``fit`` installs: the transfer
    overlaps the previous step instead of landing inside step dispatch),
    one jitted step per loader batch.  Numbers before round 3 (BASELINE.md
    "~50 imgs/s" row) were measured under the old synchronous-transfer
    semantics.

    Best-of-4 fenced epochs, mirroring the staged bench's best-of-4
    chains."""
    from mx_rcnn_tpu.data.loader import AnchorLoader

    state, step, _, cfg = build(batch, network)
    over = {}
    if workers:
        over["LOADER_WORKERS"] = workers
    if prefetch is not None:
        over["PREFETCH"] = int(prefetch)
    if over:
        cfg = cfg.replace(tpu=dataclasses.replace(cfg.tpu, **over))
    roidb = _synthetic_roidb()
    loader = AnchorLoader(roidb, cfg, batch, shuffle=True, seed=0)
    loader.put = jax.device_put  # double-buffer: transfer on prefetch thread
    try:
        # warm the jit cache for every bucket the loader can emit
        for b in loader:
            state, m = step(state, b, jax.random.PRNGKey(0))
        jax.block_until_ready(m)

        best = None
        for epoch in range(4):
            imgs = 0
            t0 = time.time()
            for i, b in enumerate(loader):
                state, m = step(state, b, jax.random.PRNGKey(i))
                imgs += batch
            _ = float(jax.device_get(m["total_loss"]))
            best = max(best or 0.0, imgs / (time.time() - t0))
    finally:
        loader.close_workers()
    return best


def bench_host_loader(batch: int, network: str = "resnet101",
                      workers: int = 0, prefetch=None):
    """Host input pipeline STANDALONE: the full AnchorLoader production
    path (cv2 resize to bucket, normalize, flip, host s2d, gt padding,
    batch assembly, prefetch queue) with no device step and no transfer —
    the pure host-side imgs/sec that ``--loader-workers`` exists to scale.
    First epoch is warmup (worker spawn, cv2 caches); best-of-3 after.

    Method-tagged "host_pipeline": this number has no device in it and
    must never land in a ledger row next to device rates."""
    from mx_rcnn_tpu.data.loader import AnchorLoader

    cfg = make_cfg(network)
    over = {}
    if workers:
        over["LOADER_WORKERS"] = workers
    if prefetch is not None:
        over["PREFETCH"] = int(prefetch)
    if over:
        cfg = cfg.replace(tpu=dataclasses.replace(cfg.tpu, **over))
    roidb = _synthetic_roidb()
    loader = AnchorLoader(roidb, cfg, batch, shuffle=True, seed=0)
    for _ in loader:  # warmup epoch
        pass
    best = None
    try:
        for _ in range(3):
            imgs = 0
            t0 = time.time()
            for _ in loader:
                imgs += batch
            best = max(best or 0.0, imgs / (time.time() - t0))
    finally:
        loader.close_workers()
    return best


def _parse_int_list(spec) -> list:
    """Comma-separated ints ("0,2,4") → [0, 2, 4]; None/"" → []."""
    if not spec:
        return []
    return [int(tok) for tok in str(spec).split(",") if tok.strip() != ""]


def bench_pipeline(args):
    """Tuned-pipeline sweep (``mx_rcnn_tpu/train/pipeline.py``): drive the
    (k steps/dispatch × loader workers × prefetch depth [× device-prep])
    matrix through the REAL train hot loop — fresh AnchorLoader per cell,
    the same producer-thread put / group-wrap hooks ``fit`` installs, one
    shared step-program cache across cells — and report per-cell imgs/s
    with the loader_wait / dispatch / fetch_stall / assembly_wait
    breakdown.  ``--auto-tune`` persists the winning cell next to the
    program cache so ``train_end2end.py --tuned-pipeline`` boots straight
    into it.  Headline value = best cell's imgs/s."""
    from mx_rcnn_tpu.train.pipeline import (PipelineSweep, parse_cells,
                                            tuned_path)

    cfg = make_cfg(args.network)
    roidb = _synthetic_roidb(args.pipeline_images)
    k_list = _parse_int_list(args.k_list) or [1, 2]
    workers_list = _parse_int_list(args.workers_list) or [0, 2]
    prefetch_list = _parse_int_list(args.prefetch_list) or [2]
    cells = parse_cells(k_list, workers_list, prefetch_list,
                        device_prep=((False, True) if args.device_prep
                                     else (False,)))
    sweep_out = args.sweep_out or os.path.join(
        os.path.dirname(tuned_path()), "pipeline_sweep.jsonl")
    sweep = PipelineSweep(cfg, roidb, batch=args.batch)
    res = sweep.sweep(cells, epochs=args.pipeline_epochs, warmup_epochs=1,
                      auto_tune=args.auto_tune, sweep_jsonl=sweep_out)
    res["sweep_jsonl"] = sweep_out
    return res


def build_infer(batch: int, network: str = "resnet101"):
    from mx_rcnn_tpu.eval.tester import Predictor
    from mx_rcnn_tpu.models import build_model, init_params
    from mx_rcnn_tpu.train.checkpoint import denormalize_for_save

    cfg = make_cfg(network)
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0), batch, (H, W))
    params = denormalize_for_save(params, cfg)
    return Predictor(model, params, cfg), cfg


def bench_infer_chain(batch: int, network: str = "resnet101"):
    """One-dispatch chained inference timing (round 5) — the same
    differenced ``lax.fori_loop`` construction as ``bench_train_chain``
    (whose docstring carries the method's full story), applied to the
    ``model.predict`` forward.  Inference has no carried state, so the
    loop carries a f32 sum folded over every output leaf (keeps the body
    alive under DCE); per-iteration epsilon image noise poisons
    loop-invariant hoisting exactly as in the train chain.  Falls back
    to the staged method when every timing pair inverts
    (pathological window)."""
    from functools import partial

    import jax.numpy as jnp

    pred, cfg = build_infer(batch, network)
    hbatch = synthetic_batch(cfg, batch)
    images = jax.device_put(hbatch["images"])
    im_info = jax.device_put(hbatch["im_info"])
    model, params = pred.model, pred.params
    key = jax.random.PRNGKey(0)

    @partial(jax.jit, static_argnames=("n",))
    def chain(n):
        def body(i, acc):
            k = jax.random.fold_in(key, i)
            imgs = images + jax.random.uniform(
                k, (), dtype=images.dtype, maxval=1e-3)
            out = model.apply({"params": params}, imgs, im_info,
                              method=model.predict)
            return acc + sum(jnp.sum(x.astype(jnp.float32))
                             for x in jax.tree.leaves(out))

        return jax.lax.fori_loop(0, n, body, jnp.float32(0.0))

    def run(n):
        return float(jax.device_get(chain(n)))  # readback = fence

    for n in (CHAIN_N1, CHAIN_N2):  # compile + warm both lengths
        acc = run(n)
    assert np.isfinite(acc)
    return _differenced_rate(run, batch,
                             lambda: bench_infer_staged(batch, network))


def bench_infer_staged(batch: int, network: str = "resnet101"):
    pred, cfg = build_infer(batch, network)
    hbatch = synthetic_batch(cfg, batch)
    images = jax.device_put(hbatch["images"])
    im_info = jax.device_put(hbatch["im_info"])
    for _ in range(WARMUP):
        out = pred.predict(images, im_info)
    jax.block_until_ready(out)

    best = None
    for _ in range(4):
        t0 = time.time()
        for _ in range(STEPS):
            out = pred.predict(images, im_info)
        _ = float(jax.device_get(out[2]).ravel()[0])  # readback fence
        dt = (time.time() - t0) / STEPS
        best = max(best or 0.0, batch / dt)
    return best


def bench_infer_loader(batch: int, network: str = "resnet101"):
    """The test.py loop: TestLoader (prefetching) + im_detect (device
    forward + full readback + per-image host bbox decode).  Per-class NMS /
    eval excluded — that is pred_eval's accounting, identical in the
    reference."""
    from mx_rcnn_tpu.data.loader import TestLoader
    from mx_rcnn_tpu.eval.tester import im_detect

    pred, cfg = build_infer(batch, network)
    roidb = _synthetic_roidb()
    loader = TestLoader(roidb, cfg, batch_size=batch)
    for b in loader:   # warm all shapes
        im_detect(pred, b)

    best = None
    for _ in range(4):   # best-of-4 epochs (see bench_train_loader note)
        imgs = 0
        t0 = time.time()
        for b in loader:
            dets = im_detect(pred, b)
            imgs += len(dets)
        best = max(best or 0.0, imgs / (time.time() - t0))
    return best


def bench_serve(batch: int, network: str = "resnet101",
                serve_e2e: bool = False, stream: bool = False):
    """Steady-state imgs/sec through the REAL serving engine — the number
    capacity planning needs (how many replicas for X qps), distinct from
    ``--mode infer``'s forward-only rate by exactly the serving tax:
    per-request cv2 resize on submitter threads, bucket routing + batch
    coalescing, device readback, and the shared per-image post-process.

    No HTTP: requests enter at ``ServeEngine.submit`` (what the frontend
    handler calls), so the measurement is transport-independent.  Four
    submitter threads feed mixed-size raw uint8 images — half landscape,
    half portrait, dimensions jittered so every request really pays
    ``resize_to_bucket`` — with per-orientation counts a multiple of
    ``batch`` (steady state runs full batches; partial-flush latency is
    loadgen's department).  503-style rejections are retried with backoff
    exactly like a real client, so backpressure throttles the feeders
    instead of crashing the bench.  Best-of-4 waves after warmup
    (pre-compiles both orientation programs)."""
    import threading

    from mx_rcnn_tpu.eval.tester import Predictor
    from mx_rcnn_tpu.models import build_model, init_params
    from mx_rcnn_tpu.serve import (RejectedError, ServeEngine, ServeOptions,
                                   warmup)
    from mx_rcnn_tpu.train.checkpoint import denormalize_for_save

    cfg = make_cfg(network)
    model = build_model(cfg)
    # init at the SCALES[0] bucket (init_params' default), not the bench's
    # fixed 608×1024 — serving dispatches bucket programs only
    params = denormalize_for_save(
        init_params(model, cfg, jax.random.PRNGKey(0), batch), cfg)
    pred = Predictor(model, params, cfg)
    engine = ServeEngine(pred, cfg, ServeOptions(
        batch_size=batch, max_delay_ms=5.0,
        max_queue=max(8 * batch, 16), serve_e2e=serve_e2e)).start()
    t_w = time.perf_counter()
    warmup(engine)
    # warmup's dummy batches run the full submit→serve path, so the end
    # of warmup IS the first-2xx-capable moment: cold_start_s = process
    # start → ready, warmup_compile_s = the compile (or AOT load) share
    warmup_compile_s = time.perf_counter() - t_w
    cold_start_s = time.perf_counter() - _PROC_T0

    short, long_ = (int(s) for s in cfg.tpu.SCALES[0])
    rng = np.random.RandomState(0)
    wave = 8 * batch  # half per orientation → full batches throughout
    imgs = []
    for i in range(wave):
        h, w = (short, long_) if i % 2 == 0 else (long_, short)
        dh, dw = rng.randint(0, 32, 2)
        imgs.append(rng.randint(0, 255, (max(h - dh, 16), max(w - dw, 16), 3),
                                dtype=np.uint8))

    def submit_retry(img):
        while True:
            try:
                return engine.submit(img, deadline_ms=0)
            except RejectedError:
                time.sleep(2e-3)

    feeders = 4
    best = None
    stream_dpf = stream_skip = None
    try:
        for _ in range(4):
            futs = [None] * wave
            t0 = time.time()

            def feed(t):
                for i in range(t, wave, feeders):
                    futs[i] = submit_retry(imgs[i])

            ts = [threading.Thread(target=feed, args=(t,))
                  for t in range(feeders)]
            for th in ts:
                th.start()
            for th in ts:
                th.join()
            for f in futs:
                f.result(timeout=600.0)
            best = max(best or 0.0, wave / (time.time() - t0))
        if stream:
            # streaming phase (--serve-stream): 4 static-motion streams
            # through a StreamManager with the skip gate on — the
            # coalescing/skip wins as counter ratios (dispatches_per_frame,
            # skip_fraction), which perf_gate scores as their OWN series,
            # never against the request/response throughput above
            from mx_rcnn_tpu.serve import StreamManager, StreamOptions

            mgr = StreamManager(engine, StreamOptions(skip_thresh=3.0,
                                                      max_skip=16))
            mgr.warmup()
            n_streams, frames = 4, 32
            rngs = [np.random.RandomState(100 + s)
                    for s in range(n_streams)]
            bases = []
            for s in range(n_streams):
                h, w = (short, long_) if s % 2 == 0 else (long_, short)
                bases.append(rngs[s].randint(0, 255, (h, w, 3),
                                             dtype=np.uint8))
            d0 = engine.counters["dispatches"]

            def run_stream(s):
                for i in range(frames):
                    f = bases[s].copy()
                    # static profile: a handful of ±1 sensor-noise pixels
                    ys = rngs[s].randint(0, f.shape[0], 8)
                    xs = rngs[s].randint(0, f.shape[1], 8)
                    f[ys, xs] = np.clip(
                        f[ys, xs].astype(np.int16) + 1, 0,
                        255).astype(np.uint8)
                    mgr.submit_frame(f"bench-{s}", i + 1,
                                     f).result(timeout=600.0)

            sts = [threading.Thread(target=run_stream, args=(s,))
                   for s in range(n_streams)]
            for th in sts:
                th.start()
            for th in sts:
                th.join()
            total = n_streams * frames
            stream_dpf = round(
                (engine.counters["dispatches"] - d0) / total, 4)
            stream_skip = round(
                mgr.counters["skipped"] / max(mgr.counters["frames"], 1),
                4)
    finally:
        # latency from the engine's own request-time histogram (submit →
        # response, over every timed wave) so the BENCH row carries p50/
        # p99 alongside throughput — "fast but slow-tailed" is visible
        h = engine.hists["serve/request_time"]
        p50, p99 = h.quantile(0.5), h.quantile(0.99)
        # boundary-crossing accounting from the engine's own counters:
        # readback_bytes_per_image is THE fused-path deliverable on a CPU
        # box (the wall-clock win is claimed on TPU), host_prep_ms is the
        # per-request submit-thread prep tax the fusion moves on device
        c = dict(engine.counters)
        readback_per_img = (c.get("readback_bytes", 0)
                            / max(c.get("served", 0), 1))
        host_prep_ms = (c.get("host_prep_ms_total", 0.0)
                        / max(c.get("requests", 0), 1))
        engine.stop()
    return (best,
            (None if p50 is None else round(p50 * 1e3, 3)),
            (None if p99 is None else round(p99 * 1e3, 3)),
            round(cold_start_s, 3), round(warmup_compile_s, 3),
            round(readback_per_img, 1), round(host_prep_ms, 3),
            stream_dpf, stream_skip)


def bench_serve_pool(batch: int, network: str = "resnet101",
                     n_models: int = 2):
    """Aggregate steady-state imgs/sec through a :class:`ModelPool` of
    ``n_models`` same-architecture, independent-weight models — the
    multi-model serving tax in one number.  Same transport-independent
    shape as ``bench_serve`` (submits enter at the engine, no HTTP) but
    requests round-robin across the per-model engines, so the measured
    rate includes cross-model dispatch interleaving and scheduler
    switches.  Gated as its own ``_mmN`` series against the
    single-model ``serve_imgs_per_sec`` floor via MULTIMODEL reports,
    never compared to it directly."""
    import threading

    from mx_rcnn_tpu.eval.tester import Predictor
    from mx_rcnn_tpu.models import build_model, init_params
    from mx_rcnn_tpu.serve import (ModelPool, RejectedError, ServeEngine,
                                   ServeOptions, warmup)
    from mx_rcnn_tpu.train.checkpoint import denormalize_for_save

    cfg = make_cfg(network)
    model = build_model(cfg)
    pool = ModelPool().start()
    mids = [f"m{i}" for i in range(n_models)]
    t_w = time.perf_counter()
    for i, mid in enumerate(mids):
        params = denormalize_for_save(
            init_params(model, cfg, jax.random.PRNGKey(i), batch), cfg)
        pred = Predictor(model, params, cfg)
        engine = ServeEngine(pred, cfg, ServeOptions(
            batch_size=batch, max_delay_ms=5.0,
            max_queue=max(8 * batch, 16)))
        engine.start(external=True)
        pool.add_model(mid, cfg, pred, engine)
        # warm THIS model before building the next (jax cache-dir order)
        warmup(engine)
    warmup_compile_s = time.perf_counter() - t_w
    cold_start_s = time.perf_counter() - _PROC_T0

    short, long_ = (int(s) for s in cfg.tpu.SCALES[0])
    rng = np.random.RandomState(0)
    # per-model, per-orientation counts stay a multiple of batch so the
    # steady state runs full batches on every engine
    wave = 8 * batch * n_models
    imgs = []
    for i in range(wave):
        h, w = (short, long_) if (i // n_models) % 2 == 0 else (long_, short)
        dh, dw = rng.randint(0, 32, 2)
        imgs.append(rng.randint(0, 255, (max(h - dh, 16), max(w - dw, 16), 3),
                                dtype=np.uint8))

    def submit_retry(i):
        engine = pool.engine_for(mids[i % n_models])
        while True:
            try:
                return engine.submit(imgs[i], deadline_ms=0)
            except RejectedError:
                time.sleep(2e-3)

    feeders = 4
    best = None
    try:
        for _ in range(4):
            futs = [None] * wave
            t0 = time.time()

            def feed(t):
                for i in range(t, wave, feeders):
                    futs[i] = submit_retry(i)

            ts = [threading.Thread(target=feed, args=(t,))
                  for t in range(feeders)]
            for th in ts:
                th.start()
            for th in ts:
                th.join()
            for f in futs:
                f.result(timeout=600.0)
            best = max(best or 0.0, wave / (time.time() - t0))
    finally:
        # worst tenant's tail, not the blended one: max over per-model
        # quantiles — the SLO a pool operator owes EACH model
        p50s, p99s = [], []
        agg = {}
        for mid in mids:
            engine = pool.engine_for(mid)
            h = engine.hists["serve/request_time"]
            q50, q99 = h.quantile(0.5), h.quantile(0.99)
            if q50 is not None:
                p50s.append(q50)
            if q99 is not None:
                p99s.append(q99)
            for k, v in engine.counters.items():
                if isinstance(v, (int, float)):
                    agg[k] = agg.get(k, 0) + v
        readback_per_img = (agg.get("readback_bytes", 0)
                            / max(agg.get("served", 0), 1))
        host_prep_ms = (agg.get("host_prep_ms_total", 0.0)
                        / max(agg.get("requests", 0), 1))
        sched = dict(pool.counters)
        pool.stop()
    pool_doc = {
        "models": n_models,
        "sched_batches": sched["sched_batches"],
        "sched_switches": sched["sched_switches"],
        "switches_per_batch": round(
            sched["sched_switches"] / max(sched["sched_batches"], 1), 4),
    }
    return (best,
            (round(max(p50s) * 1e3, 3) if p50s else None),
            (round(max(p99s) * 1e3, 3) if p99s else None),
            round(cold_start_s, 3), round(warmup_compile_s, 3),
            round(readback_per_img, 1), round(host_prep_ms, 3), pool_doc)


def bench_serve_cascade(batch: int, network: str = "resnet101",
                        thresh: float = 0.5):
    """Steady-state imgs/sec through a two-model cascade (ISSUE 19):
    every request enters at ``CascadeRouter.submit`` (what the frontend
    calls with --cascade active), answers from the small model unless
    the on-device hardness gate escalates it to the big sibling.  Both
    engines run the fused serve_e2e program — the gate consumes its
    on-device detections.  Same transport-independent shape as
    ``bench_serve``; the measured rate includes the gate dispatch and
    every escalated frame's second (staged-reuse) pass.  Reported as
    ``serve_imgs_per_sec_cascade`` with ``escalation_rate`` alongside —
    its OWN baseline series, never compared to the single-model or
    pool rows (the throughput-vs-big-only floor is loadgen's CASCADE
    report, where both sides run on the same box in the same run)."""
    import threading

    from mx_rcnn_tpu.eval.tester import Predictor
    from mx_rcnn_tpu.models import build_model, init_params
    from mx_rcnn_tpu.serve import (CascadeRouter, ModelPool, RejectedError,
                                   ServeEngine, ServeOptions, warmup)
    from mx_rcnn_tpu.train.checkpoint import denormalize_for_save

    cfg = make_cfg(network)
    model = build_model(cfg)
    pool = ModelPool().start()
    mids = ("small", "big")
    t_w = time.perf_counter()
    for i, mid in enumerate(mids):
        params = denormalize_for_save(
            init_params(model, cfg, jax.random.PRNGKey(i), batch), cfg)
        pred = Predictor(model, params, cfg)
        engine = ServeEngine(pred, cfg, ServeOptions(
            batch_size=batch, max_delay_ms=5.0,
            max_queue=max(8 * batch, 16), serve_e2e=True))
        engine.start(external=True)
        pool.add_model(mid, cfg, pred, engine)
        warmup(engine)
    cascade = CascadeRouter(pool, "small", "big", thresh=thresh)
    cascade.warmup()  # the gate program compiles before traffic too
    pool.cascade = cascade
    warmup_compile_s = time.perf_counter() - t_w
    cold_start_s = time.perf_counter() - _PROC_T0

    short, long_ = (int(s) for s in cfg.tpu.SCALES[0])
    rng = np.random.RandomState(0)
    wave = 8 * batch
    imgs = []
    for i in range(wave):
        h, w = (short, long_) if i % 2 == 0 else (long_, short)
        dh, dw = rng.randint(0, 32, 2)
        imgs.append(rng.randint(0, 255, (max(h - dh, 16), max(w - dw, 16), 3),
                                dtype=np.uint8))

    def submit_retry(img):
        while True:
            try:
                return cascade.submit(img, deadline_ms=0)
            except RejectedError:
                time.sleep(2e-3)

    feeders = 4
    best = None
    try:
        for _ in range(4):
            futs = [None] * wave
            t0 = time.time()

            def feed(t):
                for i in range(t, wave, feeders):
                    futs[i] = submit_retry(imgs[i])

            ts = [threading.Thread(target=feed, args=(t,))
                  for t in range(feeders)]
            for th in ts:
                th.start()
            for th in ts:
                th.join()
            for f in futs:
                f.result(timeout=600.0)
            best = max(best or 0.0, wave / (time.time() - t0))
    finally:
        # worst engine's tail (the pool convention) + aggregate boundary
        # accounting across both cascade members
        p50s, p99s = [], []
        agg = {}
        for mid in mids:
            engine = pool.engine_for(mid)
            h = engine.hists["serve/request_time"]
            q50, q99 = h.quantile(0.5), h.quantile(0.99)
            if q50 is not None:
                p50s.append(q50)
            if q99 is not None:
                p99s.append(q99)
            for k, v in engine.counters.items():
                if isinstance(v, (int, float)):
                    agg[k] = agg.get(k, 0) + v
        readback_per_img = (agg.get("readback_bytes", 0)
                            / max(agg.get("served", 0), 1))
        host_prep_ms = (agg.get("host_prep_ms_total", 0.0)
                        / max(agg.get("requests", 0), 1))
        cascade_doc = cascade.metrics()
        pool.stop()
    return (best,
            (round(max(p50s) * 1e3, 3) if p50s else None),
            (round(max(p99s) * 1e3, 3) if p99s else None),
            round(cold_start_s, 3), round(warmup_compile_s, 3),
            round(readback_per_img, 1), round(host_prep_ms, 3),
            cascade_doc)


def bench_infer_mask(batch: int, network: str = "resnet101_fpn_mask"):
    """Full Mask R-CNN eval loop (VERDICT round-2 item 6): pred_eval with
    with_masks=True — forward + per-class NMS + mask chunk drain + 28×28
    paste + RLE encode + segm scoring, over the synthetic imdb.  Times the
    second pred_eval call (first warms every jit shape incl. the mask
    chunks); reports imgs/sec of the WHOLE loop, the number test.py users
    experience."""
    from mx_rcnn_tpu.data.synthetic import SyntheticDataset
    from mx_rcnn_tpu.data.loader import TestLoader
    from mx_rcnn_tpu.eval.tester import pred_eval

    pred, cfg = build_infer(batch, network)
    assert cfg.network.HAS_MASK, f"{network} has no mask head"
    ds = SyntheticDataset(num_images=24, height=600, width=800)
    roidb = ds.gt_roidb()
    pred_eval(pred, TestLoader(roidb, cfg, batch_size=batch), ds,
              with_masks=True)  # warm
    best = None
    for _ in range(2):
        t0 = time.time()
        pred_eval(pred, TestLoader(roidb, cfg, batch_size=batch), ds,
                  with_masks=True)
        best = max(best or 0.0, len(roidb) / (time.time() - t0))
    return best


def bench_eval(batch: int, network: str = "resnet101", num_images: int = 24):
    """Serial vs pipelined vs --device-postprocess through the REAL
    ``pred_eval`` loop over the synthetic imdb — the three eval variants
    one row apart, on the same box, same warm program cache.  Warms every
    jit shape first (incl. the fused device-postprocess program), then
    takes best-of-2 per variant, interleaved so drift hits all three
    equally.  Headline value = pipelined rate; the serial rate is the
    denominator of ``speedup_vs_serial``, which perf_gate scores against
    an absolute floor of 1.0 ("the overlap machinery must not lose to
    the loop it replaced")."""
    from mx_rcnn_tpu.data.loader import TestLoader
    from mx_rcnn_tpu.data.synthetic import SyntheticDataset
    from mx_rcnn_tpu.eval.tester import pred_eval

    pred, cfg = build_infer(batch, network)
    ds = SyntheticDataset(num_images=num_images, height=600, width=800)
    roidb = ds.gt_roidb()

    def run(**kw):
        t0 = time.time()
        pred_eval(pred, TestLoader(roidb, cfg, batch_size=batch), ds,
                  with_masks=cfg.network.HAS_MASK, **kw)
        return len(roidb) / (time.time() - t0)

    run(inflight=2)                             # warm the host-NMS shapes
    run(inflight=2, device_postprocess=True)    # warm the fused program
    rates = {"serial": 0.0, "pipelined": 0.0, "device_post": 0.0}
    for _ in range(2):
        rates["serial"] = max(rates["serial"], run(inflight=0))
        rates["pipelined"] = max(rates["pipelined"], run(inflight=2))
        rates["device_post"] = max(
            rates["device_post"], run(inflight=2, device_postprocess=True))
    return rates


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="train",
                    choices=["train", "loader", "train-loader", "infer",
                             "infer-loader", "infer-mask", "serve",
                             "pipeline", "eval"])
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--loader-workers", type=int, default=0,
                    dest="loader_workers",
                    help="loader/train-loader modes: host input-pipeline "
                         "worker processes (0 = the serial producer); "
                         "non-zero suffixes the metric with _w{N}")
    ap.add_argument("--workers-list", default="", dest="workers_list",
                    help="comma list of worker counts, e.g. 0,2,4 — "
                         "loader/train-loader: sweep standalone cells "
                         "(headline = best, every cell in the JSON); "
                         "pipeline: the matrix's workers axis "
                         "(default 0,2)")
    ap.add_argument("--prefetch-list", default="", dest="prefetch_list",
                    help="comma list of prefetch queue depths — "
                         "loader/train-loader sweep axis / pipeline "
                         "matrix axis (default: config PREFETCH; "
                         "pipeline default 2)")
    ap.add_argument("--k-list", default="", dest="k_list",
                    help="pipeline mode: comma list of steps-per-dispatch "
                         "group sizes (default 1,2)")
    ap.add_argument("--auto-tune", action="store_true", dest="auto_tune",
                    help="pipeline mode: persist the winning cell next to "
                         "the program cache (train_end2end.py/"
                         "train_alternate.py --tuned-pipeline reads it)")
    ap.add_argument("--device-prep", action="store_true", dest="device_prep",
                    help="pipeline mode: sweep device-side preprocessing "
                         "as a matrix axis (each k×w×p cell runs host-prep "
                         "AND device-prep)")
    ap.add_argument("--serve-e2e", action="store_true", dest="serve_e2e",
                    help="serve mode: run the engine with the fused "
                         "single-dispatch serve_e2e program (staged uint8 "
                         "in, (B, cap, 6) detections out).  The metric is "
                         "suffixed _e2e — its own baseline series, never "
                         "compared against the unfused engine rows")
    ap.add_argument("--serve-stream", action="store_true",
                    dest="serve_stream",
                    help="serve mode: also run a streaming phase (4 "
                         "static-motion streams through a StreamManager "
                         "with the frame-delta gate on) and report "
                         "dispatches_per_frame + skip_fraction as their "
                         "own gated series")
    ap.add_argument("--serve-models", type=int, default=0,
                    dest="serve_models",
                    help="serve mode: run N same-architecture, "
                         "independent-weight models behind one ModelPool "
                         "and report AGGREGATE imgs/sec (requests round-"
                         "robin across models).  Metric suffixed _mmN — "
                         "its own series; the JSON carries the pool's "
                         "scheduler counters")
    ap.add_argument("--serve-cascade", action="store_true",
                    dest="serve_cascade",
                    help="serve mode: run a small:big cascade behind a "
                         "CascadeRouter (both engines serve_e2e, the "
                         "on-device hardness gate escalating) and report "
                         "imgs/sec as serve_imgs_per_sec_cascade with "
                         "escalation_rate alongside — its own series, "
                         "never scored against non-cascade rows")
    ap.add_argument("--pipeline-images", type=int, default=32,
                    dest="pipeline_images",
                    help="pipeline mode: synthetic roidb size per epoch")
    ap.add_argument("--pipeline-epochs", type=int, default=1,
                    dest="pipeline_epochs",
                    help="pipeline mode: measured epochs per cell (one "
                         "extra warmup epoch always runs first)")
    ap.add_argument("--sweep-out", default="", dest="sweep_out",
                    help="pipeline mode: per-cell JSONL path (telemetry-"
                         "meta-shaped rows; scripts/telemetry_report.py "
                         "renders the table).  Default: pipeline_sweep."
                         "jsonl next to the program cache")
    ap.add_argument("--network", default=None,
                    help="config preset (e.g. resnet101, resnet101_fpn, "
                         "resnet101_fpn_mask); non-default appears in the "
                         "metric name")
    ap.add_argument("--cfg", action="append", default=[],
                    help="config override PATH=VALUE (python literal; "
                         "common.py syntax), e.g. "
                         "--cfg TRAIN__RPN_ASSIGN_IOU_BF16=True — for "
                         "A/B step-time measurements of ledger levers")
    ap.add_argument("--opt-acc-ab", action="store_true", dest="opt_acc_ab",
                    help="train mode: A/B the optimizer accumulator dtype "
                         "in ONE invocation — the chain bench runs twice "
                         "(TRAIN__OPT_ACC_DTYPE float32 then bfloat16) "
                         "and the JSON carries both rates plus the "
                         "ms/step delta, pinning (or retiring) the "
                         "config.py '−0.26 ms measured' claim.  Headline "
                         "value/baseline compare use the f32 run")
    ap.add_argument("--legacy-dispatch", action="store_true",
                    help="train AND infer modes: use the staged "
                         "async-dispatch method (subject to host "
                         "dispatch-rate noise) instead of the "
                         "one-dispatch fori_loop chain")
    ap.add_argument("--telemetry-dir", default="", dest="telemetry_dir",
                    help="stream the run's telemetry (JSONL events + "
                         "summary JSON) here; the loader/infer-loader/"
                         "infer-mask modes emit the same per-phase spans "
                         "as real training/eval (the instrumented loader "
                         "and tester run inside the measured loop)")
    ap.add_argument("--obs-port", type=int, default=0, dest="obs_port",
                    help="live Prometheus /metrics + /healthz on "
                         "127.0.0.1:PORT while the bench runs "
                         "(telemetry/obs.py; 0 = off)")
    args = ap.parse_args()
    from mx_rcnn_tpu.compile import setup_compile_cache
    from mx_rcnn_tpu.tools.common import parse_cfg_overrides

    setup_compile_cache()

    CFG_OVERRIDES.update(parse_cfg_overrides(args.cfg))
    if args.network is None:
        # per-mode default: an explicitly passed network is never rewritten
        args.network = ("resnet101_fpn_mask" if args.mode == "infer-mask"
                        else "resnet101")
    from mx_rcnn_tpu import telemetry
    from mx_rcnn_tpu.tools.common import start_observability

    obs = start_observability(args, "bench",
                              run_meta={"mode": args.mode,
                                        "batch": args.batch,
                                        "network": args.network},
                              configure_telemetry=True)

    tel = telemetry.get()
    t_bench = time.perf_counter()
    infer_method = None
    opt_acc = None
    sweep_cells = None
    pipe = None
    eval_rates = None
    if args.mode == "train":
        fn = bench_train_staged if args.legacy_dispatch else bench_train_chain
        if args.opt_acc_ab:
            ab = {}
            for dt in ("float32", "bfloat16"):
                CFG_OVERRIDES["TRAIN__OPT_ACC_DTYPE"] = dt
                ab[dt] = fn(args.batch, args.network)
            CFG_OVERRIDES.pop("TRAIN__OPT_ACC_DTYPE")
            value = ab["float32"]
            ms = {dt: args.batch / v * 1e3 for dt, v in ab.items()}
            opt_acc = {
                "f32_imgs_per_sec": round(ab["float32"], 3),
                "bf16_imgs_per_sec": round(ab["bfloat16"], 3),
                "f32_ms_per_step": round(ms["float32"], 3),
                "bf16_ms_per_step": round(ms["bfloat16"], 3),
                # positive = bf16 accumulator is faster by this much
                "delta_ms_per_step": round(ms["float32"]
                                           - ms["bfloat16"], 3),
            }
        else:
            value = fn(args.batch, args.network)
        metric = "train_imgs_per_sec_per_chip"
    elif args.mode in ("loader", "train-loader"):
        fn = (bench_host_loader if args.mode == "loader"
              else bench_train_loader)
        metric = ("loader_imgs_per_sec_host" if args.mode == "loader"
                  else "train_imgs_per_sec_loader_inclusive")
        wl = _parse_int_list(args.workers_list)
        pl = _parse_int_list(args.prefetch_list)
        if wl or pl:
            # reproducible standalone sweep: every (workers, prefetch)
            # cell in the JSON, best as the headline.  _sweep keys the
            # metric apart from single-cell rows of the same mode.
            sweep_cells = []
            for w in (wl or [args.loader_workers]):
                for p in (pl or [None]):
                    v = fn(args.batch, args.network, w, p)
                    sweep_cells.append({
                        "workers": w,
                        "prefetch": p,
                        "imgs_per_sec": round(v, 3)})
            value = max(c["imgs_per_sec"] for c in sweep_cells)
            metric += "_sweep"
        else:
            value = fn(args.batch, args.network, args.loader_workers)
            if args.loader_workers:
                metric += f"_w{args.loader_workers}"
        if args.mode == "loader":
            infer_method = "host_pipeline"  # no device in this number:
            # never comparable to device/train/serve rows
    elif args.mode == "pipeline":
        pipe = bench_pipeline(args)
        value = pipe["best"]["imgs_per_sec"]
        metric = "train_imgs_per_sec_pipeline"
        infer_method = "pipeline"  # loader-inclusive real-hot-loop sweep:
        # never comparable to chain/staged dispatch-free rows
    elif args.mode == "infer":
        fn = bench_infer_staged if args.legacy_dispatch else bench_infer_chain
        value = fn(args.batch, args.network)
        metric = "infer_imgs_per_sec"
        # name the method in the artifact: the staged-method BASELINE.md
        # rows share this metric name, and a chain number silently
        # compared against them would cross methods (the train path
        # guards this with value/value_chain + baseline_method)
        infer_method = "staged" if args.legacy_dispatch else "chain"
    elif args.mode == "infer-mask":
        value = bench_infer_mask(args.batch, args.network)
        metric = "infer_imgs_per_sec_mask_eval"
    elif args.mode == "serve":
        serve_pool_doc = None
        serve_cascade_doc = None
        if args.serve_cascade:
            if args.serve_e2e or args.serve_stream or args.serve_models:
                raise SystemExit("--serve-cascade is exclusive with "
                                 "--serve-e2e / --serve-stream / "
                                 "--serve-models")
            (value, serve_p50_ms, serve_p99_ms, serve_cold_start_s,
             serve_warmup_s, serve_readback_b, serve_prep_ms,
             serve_cascade_doc) = bench_serve_cascade(
                 args.batch, args.network)
            serve_stream_dpf = serve_stream_skip = None
            metric = "serve_imgs_per_sec_cascade"
        elif args.serve_models >= 2:
            if args.serve_e2e or args.serve_stream:
                raise SystemExit("--serve-models is exclusive with "
                                 "--serve-e2e / --serve-stream")
            (value, serve_p50_ms, serve_p99_ms, serve_cold_start_s,
             serve_warmup_s, serve_readback_b, serve_prep_ms,
             serve_pool_doc) = bench_serve_pool(
                 args.batch, args.network, args.serve_models)
            serve_stream_dpf = serve_stream_skip = None
            metric = f"serve_imgs_per_sec_mm{args.serve_models}"
        else:
            (value, serve_p50_ms, serve_p99_ms, serve_cold_start_s,
             serve_warmup_s, serve_readback_b, serve_prep_ms,
             serve_stream_dpf, serve_stream_skip) = bench_serve(
                 args.batch, args.network, serve_e2e=args.serve_e2e,
                 stream=args.serve_stream)
            metric = ("serve_imgs_per_sec_e2e" if args.serve_e2e
                      else "serve_imgs_per_sec")
        infer_method = "engine"  # not comparable to forward-only rows
    elif args.mode == "eval":
        eval_rates = bench_eval(args.batch, args.network)
        value = eval_rates["pipelined"]
        metric = "eval_imgs_per_sec"
        infer_method = "pred_eval"  # whole-eval-loop rate: never
        # comparable to forward-only or loader-only rows
    else:
        value = bench_infer_loader(args.batch, args.network)
        metric = "infer_imgs_per_sec_loader_inclusive"
    # whole-mode wall (warmup + compile + timed loops) and the headline
    # result, in the run's own schema — the loader/tester phase spans from
    # the measured loop land in the same stream
    tel.add(f"bench/{args.mode}", time.perf_counter() - t_bench)
    if args.batch != 1:
        metric += f"_b{args.batch}"
    if args.network != "resnet101":
        metric += f"_{args.network}"
    if args.cfg:
        metric += "_ab"  # overridden config: never a headline number
    if opt_acc is not None:
        metric += "_optacc_ab"  # two-config A/B: never a headline number

    vs = None
    baseline_method = None
    baseline_recorded = False
    if (args.mode == "train" and args.batch == 1
            and args.network == "resnet101" and not args.cfg
            and opt_acc is None):
        # method-consistent ratio (round-4 VERDICT weakness 3): chain-
        # method runs divide by the chain-method baseline ('value_chain',
        # the round-4 clean-window measurement), staged runs by the
        # round-1 staged baseline ('value') — a cross-method ratio mixes
        # a dispatch-free numerator with a dispatch-taxed denominator and
        # reads as speedup that is really measurement
        key = "value" if args.legacy_dispatch else "value_chain"
        base = None
        if os.path.exists(BASELINE_FILE):
            with open(BASELINE_FILE) as f:
                base_doc = json.load(f)
            base = base_doc.get(key)
            if base is None:  # first run of this method: record it
                base_doc[key] = value
                with open(BASELINE_FILE, "w") as f:
                    json.dump(base_doc, f)
        else:
            with open(BASELINE_FILE, "w") as f:
                json.dump({"metric": metric, key: value,
                           "hardware": str(jax.devices()[0]),
                           "config": "resnet101 faster-rcnn end2end 608x1024 b1"},
                          f)
        if base is not None:
            vs = round(value / base, 3)
        else:
            # this run IS the baseline it just wrote — a 1.0 here would
            # read as measured parity in the ledger when nothing was
            # compared; say so explicitly instead
            vs = None
            baseline_recorded = True
        baseline_method = "staged" if args.legacy_dispatch else "chain"
    elif args.mode == "pipeline" and not args.cfg:
        # the pipeline series gets its own baseline key per (batch,
        # network): the number is loader-inclusive and box-dependent,
        # never comparable to the dispatch-free chain/staged train rows
        # (and perf_gate groups by baseline_method, so the r05 chain row
        # is never scored against this series)
        key = "value_pipeline"
        if args.batch != 1:
            key += f"_b{args.batch}"
        if args.network != "resnet101":
            key += f"_{args.network}"
        base_doc = {}
        if os.path.exists(BASELINE_FILE):
            with open(BASELINE_FILE) as f:
                base_doc = json.load(f)
        base = base_doc.get(key)
        if base is None:  # first pipeline run of this shape: record it
            base_doc[key] = value
            with open(BASELINE_FILE, "w") as f:
                json.dump(base_doc, f)
            baseline_recorded = True
        else:
            vs = round(value / base, 3)
        baseline_method = "pipeline"
    elif args.mode == "eval":
        # eval gets its own baseline series per (batch, network): the
        # number is a whole-pred_eval rate (loader + forward + NMS +
        # scoring), never comparable to the other series.  The _ab
        # (--cfg) variants are unscored like everywhere else, but the
        # speedup_vs_serial floor row still gates them — "pipelined
        # beats serial" must hold on any config.
        if not args.cfg:
            key = "value_eval"
            if args.batch != 1:
                key += f"_b{args.batch}"
            if args.network != "resnet101":
                key += f"_{args.network}"
            base_doc = {}
            if os.path.exists(BASELINE_FILE):
                with open(BASELINE_FILE) as f:
                    base_doc = json.load(f)
            base = base_doc.get(key)
            if base is None:  # first eval run of this shape: record it
                base_doc[key] = value
                with open(BASELINE_FILE, "w") as f:
                    json.dump(base_doc, f)
                baseline_recorded = True
            else:
                vs = round(value / base, 3)
            baseline_method = "pred_eval"
    elif args.mode == "serve" and args.serve_cascade and not args.cfg:
        # the cascade serve series gets its own record-on-first-run
        # baseline per (batch, network): a blended small/big rate is
        # never comparable to single-model or pool rows, and perf_gate
        # groups by baseline_method so they never cross
        key = "value_serve_cascade"
        if args.batch != 1:
            key += f"_b{args.batch}"
        if args.network != "resnet101":
            key += f"_{args.network}"
        base_doc = {}
        if os.path.exists(BASELINE_FILE):
            with open(BASELINE_FILE) as f:
                base_doc = json.load(f)
        base = base_doc.get(key)
        if base is None:  # first cascade run of this shape: record it
            base_doc[key] = value
            with open(BASELINE_FILE, "w") as f:
                json.dump(base_doc, f)
            baseline_recorded = True
        else:
            vs = round(value / base, 3)
        baseline_method = "cascade"

    out = {
        "metric": metric,
        "value": round(value, 3),
        "unit": "imgs/sec",
        "vs_baseline": vs,
    }
    if baseline_method is not None:
        out["baseline_method"] = baseline_method
    if baseline_recorded:
        out["baseline_recorded"] = True
    if infer_method is not None:
        out["method"] = infer_method
    if args.mode == "serve":
        out["p50_ms"] = serve_p50_ms
        out["p99_ms"] = serve_p99_ms
        # scripts/perf_gate.py expands these into direction=down rows, so
        # a cold-start regression (lost AOT warm start) fails the gate
        out["cold_start_s"] = serve_cold_start_s
        out["warmup_compile_s"] = serve_warmup_s
        # direction=down in perf_gate too: the e2e readback shrink (full
        # (R,K)+(R,4K) tensors → (B,cap,6) detections) can never silently
        # regress, and host_prep_ms pins the submit-thread prep tax
        out["readback_bytes_per_image"] = serve_readback_b
        out["host_prep_ms"] = serve_prep_ms
        # streaming phase (--serve-stream only): perf_gate expands these
        # into a direction=down dispatches_per_frame series and a
        # skip_fraction FLOOR row — their own families, never scored
        # against the request/response rows (the BENCH_r08 precedent)
        if serve_stream_dpf is not None:
            out["dispatches_per_frame"] = serve_stream_dpf
        if serve_stream_skip is not None:
            out["skip_fraction"] = serve_stream_skip
        # multi-model phase (--serve-models): the pool's scheduler
        # counters ride along for the MULTIMODEL evidence trail
        if serve_pool_doc is not None:
            out["pool"] = serve_pool_doc
        # cascade phase (--serve-cascade): escalation_rate is its own
        # ride-along series (keyed by the cascade metric — validated,
        # never scored against non-cascade rows), the router's counters
        # and gate-time quantiles alongside for the evidence trail
        if serve_cascade_doc is not None:
            out["escalation_rate"] = serve_cascade_doc.get(
                "escalation_rate")
            out["cascade"] = serve_cascade_doc
    if opt_acc is not None:
        out["opt_acc"] = opt_acc
    if eval_rates is not None:
        # one row, three variants (satellite contract: serial vs
        # pipelined vs device-postprocess on the same box); perf_gate
        # expands speedup_vs_serial into an absolute-floor row
        out["eval"] = {
            "serial_imgs_per_sec": round(eval_rates["serial"], 3),
            "pipelined_imgs_per_sec": round(eval_rates["pipelined"], 3),
            "device_post_imgs_per_sec": round(eval_rates["device_post"], 3),
            "speedup_vs_serial": round(
                eval_rates["pipelined"] / max(eval_rates["serial"], 1e-9),
                4),
        }
    if sweep_cells is not None:
        out["cells"] = sweep_cells
    if pipe is not None:
        reg = pipe.get("registry", {})
        out["pipeline"] = {
            "best": pipe["best"],
            "cells": pipe["cells"],
            # the registry proof: programs stays flat across cells that
            # share k (no per-cell recompiles), aot_hit counts warm boots
            "programs": len(reg.get("programs", [])),
            "registry_counters": reg.get("counters", {}),
            "sweep_jsonl": pipe.get("sweep_jsonl"),
        }
        if "tuned_file" in pipe:
            out["pipeline"]["tuned_file"] = pipe["tuned_file"]
            out["pipeline"]["tuned"] = pipe["tuned"]
    if tel.enabled:
        tel.gauge(f"bench/{metric}", value)
    obs.close(extra={"bench": out})
    print(json.dumps(out))


if __name__ == "__main__":
    main()
