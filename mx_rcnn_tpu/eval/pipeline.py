"""Overlapped eval pipeline: device forward N+1 runs while host
post-process N decodes/NMSes/pastes.

The serial ``pred_eval`` loop interleaves two resources that have no data
dependency across batches: the device (forward) and the host (decode +
per-class NMS + mask paste).  Each is idle while the other works, so the
eval rate is the SUM of the two costs.  This module saturates both:

* ``dispatch`` — jax's async dispatch queues batch N+1's forward
  immediately; ``copy_to_host_async`` starts the d2h transfer of batch
  N's outputs in the background.
* a bounded in-flight window (``inflight``) throttles dispatch so device
  memory holds at most that many batches' outputs (plus their captured
  pyramids on mask configs).
* host post-process runs on a ``host_workers``-wide thread pool; results
  are INDEX-addressed into ``all_boxes[k][image_index]``, so completion
  order cannot change the output — ``all_boxes``/``all_masks`` are
  bit-identical to the serial loop at any depth (pinned by
  ``tests/test_eval_pipeline.py``).

Mask configs: ``Predictor.predict`` caches one batch's pyramid and the
next dispatch overwrites it — the classic stale-cache hazard under
overlap.  ``Predictor.capture_feats()`` takes a per-batch handle
``(feats, token)`` right after each dispatch; the host task hands it back
via ``predict_masks_*(..., feats=...)`` so batch N's mask pass reads
batch N's pyramid even while N+1 owns the cache.  Predictors without
``capture_feats`` (duck-typed test stubs) fall back to the token
discipline, which fails loudly — never silently wrong masks.

``inflight=1`` degenerates to the serial structure (forward N+1 waits for
N's host work); ``inflight=2`` is classic double-buffering and is the
default (``cfg.tpu.EVAL_INFLIGHT``).
"""

from __future__ import annotations

import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.ops.postprocess import (device_dets_to_per_class,
                                         image_boxes, per_class_nms)


class _InFlight:
    """One dispatched-but-not-yet-post-processed batch."""

    __slots__ = ("batch", "arrays", "feats", "token", "n_valid")

    def __init__(self, batch, arrays, feats, token, n_valid):
        self.batch = batch
        self.arrays = arrays      # device arrays; dropped after readback
        self.feats = feats        # captured pyramid (mask configs) or None
        self.token = token
        self.n_valid = n_valid


def run_pipelined(predictor, test_loader, *, all_boxes, all_masks, imdb,
                  num_classes, max_per_image, thresh, nms_thresh, vis,
                  with_masks, device_postprocess, inflight, host_workers,
                  progress) -> dict:
    """Drive the overlapped loop; fills ``all_boxes``/``all_masks`` in
    place and returns the overlap-accounting stats dict ``pred_eval``
    folds into the ``eval_pipeline`` telemetry meta record."""
    from mx_rcnn_tpu.eval.tester import _mask_pass, save_vis

    tel = telemetry.get()
    mode = "pipelined+devpost" if device_postprocess else "pipelined"
    inflight = max(int(inflight), 1)
    can_capture = with_masks and hasattr(predictor, "capture_feats")
    window: deque = deque()   # dispatched, outputs still on device
    pending: deque = deque()  # host futures, submission order
    done = 0
    loader_wait = 0.0
    readback_wait = 0.0
    host_post = 0.0
    post_wait = 0.0
    pool = ThreadPoolExecutor(max_workers=max(int(host_workers), 1),
                              thread_name_prefix="eval-post")

    def dispatch(batch) -> None:
        with tel.span("eval/forward"):
            if device_postprocess:
                arrays = predictor.predict_detections(
                    batch["images"], batch["im_info"], max_per_image,
                    thresh)
            else:
                arrays = predictor.predict(batch["images"],
                                           batch["im_info"])[:4]
        if can_capture:
            feats, token = predictor.capture_feats()
        else:
            feats, token = None, getattr(predictor, "feats_token", None)
        arrays = tuple(arrays)
        for a in arrays:
            try:
                a.copy_to_host_async()  # d2h overlaps the next forward
            except AttributeError:
                pass  # duck-typed stubs may return plain numpy
        bv = batch.get("batch_valid")
        n_valid = (int(np.sum(bv)) if bv is not None
                   else int(arrays[0].shape[0]))
        window.append(_InFlight(batch, arrays, feats, token, n_valid))

    def host_task(entry: _InFlight, host) -> tuple:
        t_start = time.perf_counter()
        batch = entry.batch
        indices = batch["indices"]
        im_info = np.asarray(batch["im_info"])
        rows = []
        t_dec = 0.0
        t_nms = 0.0
        for b in range(entry.n_valid):
            i = int(indices[b])
            if device_postprocess:
                t = time.perf_counter()
                dets_pc = device_dets_to_per_class(host[0][b], host[1][b],
                                                   num_classes)
                t_dec += time.perf_counter() - t
            else:
                rois, roi_valid, cls_prob, deltas = host
                t = time.perf_counter()
                boxes = image_boxes(rois[b], deltas[b], im_info[b])
                t_mid = time.perf_counter()
                t_dec += t_mid - t
                dets_pc = per_class_nms(cls_prob[b], boxes, roi_valid[b],
                                        num_classes, thresh, nms_thresh,
                                        max_per_image)
                t_nms += time.perf_counter() - t_mid
            for k in range(1, num_classes):
                all_boxes[k][i] = dets_pc[k]
            if vis:
                save_vis(test_loader.roidb[i], all_boxes, num_classes,
                         imdb.classes, i)
            rows.append(dets_pc)
        # same span names as the serial loop (pinned by the telemetry
        # test) — measured here, recorded via the non-context form
        tel.add("eval/decode", t_dec, n=max(entry.n_valid, 1))
        tel.add("eval/nms", t_nms, n=max(entry.n_valid, 1))
        if with_masks:
            with tel.span("eval/mask_pass"):
                _mask_pass(predictor, batch, rows, all_boxes, all_masks,
                           test_loader.roidb, max_per_image, num_classes,
                           token=entry.token, feats=entry.feats)
        return entry.n_valid, time.perf_counter() - t_start

    def finish_oldest() -> None:
        """Readback the oldest in-flight batch (the only place the main
        thread blocks on the device) and hand it to the pool."""
        nonlocal readback_wait
        entry = window.popleft()
        t = time.perf_counter()
        with tel.span("eval/readback"):
            host = tuple(np.asarray(a) for a in entry.arrays)
        readback_wait += time.perf_counter() - t
        entry.arrays = None  # release the device buffers
        pending.append(pool.submit(host_task, entry, host))

    def account(res) -> None:
        nonlocal done, host_post
        n, dt = res
        done += n
        host_post += dt
        progress.update(done, tel)

    def reap_done() -> None:
        while pending and pending[0].done():
            account(pending.popleft().result())

    def wait_oldest() -> None:
        nonlocal post_wait
        t = time.perf_counter()
        res = pending.popleft().result()
        post_wait += time.perf_counter() - t
        account(res)

    try:
        it = iter(test_loader)
        while True:
            t_wait = time.perf_counter()
            try:
                batch = next(it)
            except StopIteration:
                break
            dt_wait = time.perf_counter() - t_wait
            loader_wait += dt_wait
            tel.add("eval/loader_wait", dt_wait)
            reap_done()
            # bounded window: count both device-resident batches and
            # not-yet-finished host work against the in-flight budget
            while len(window) + len(pending) >= inflight:
                if window:
                    finish_oldest()
                else:
                    wait_oldest()
            dispatch(batch)
            # eagerly hand all but the newest batch to the pool: its
            # readback only waits on an already-dispatched forward, and
            # host work starts while the newest forward runs
            while len(window) > 1:
                finish_oldest()
            tel.gauge("eval/inflight_depth", len(window) + len(pending))
        while window:
            finish_oldest()
        while pending:
            wait_oldest()
    finally:
        pool.shutdown(wait=True)
    return {"mode": mode, "images": done, "loader_wait_s": loader_wait,
            "readback_wait_s": readback_wait, "host_post_s": host_post,
            "post_wait_s": post_wait, "inflight": inflight,
            "host_workers": int(host_workers)}
