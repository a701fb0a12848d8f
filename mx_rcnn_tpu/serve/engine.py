"""Online serving engine: bucket-aware dynamic batcher over ``Predictor``.

The offline path (``pred_eval``) fills batches from a dataset; online
traffic arrives one image at a time, at arbitrary
sizes, and Faster R-CNN inference is throughput-bound on batch fill.
Iteration-level dynamic batching (the Clipper recipe, Crankshaw et al.,
NSDI 2017) is exactly what the static-shape bucket design enables: every
request is resized+padded into one of a small set of pre-compiled bucket
shapes (``data.prepare_image``, the same chain the eval loader runs), so
mixed-size traffic coalesces into full batches of a handful of jit
programs with zero steady-state recompiles.

Mechanics:

* ``submit`` preps the image ON THE CALLER'S THREAD (frontend request
  threads parallelize the cv2 resize, the host-side cost), routes it to
  its orientation bucket queue, and returns a :class:`ServeFuture`.
* One dispatcher thread owns the device: it flushes a bucket when it has
  ``batch_size`` requests, or when its oldest request has waited
  ``max_delay_ms`` (the latency/throughput knob — 0 serves singletons
  immediately, larger values trade head-of-line latency for fill).
  A batch is assembled where its images are made: each bucket fills
  reused **staging batches** — arrays of the predict program's whole
  input shape, allocated and written once — and ``submit`` copies its
  prepared image into its own row on the caller's thread.  A batch is
  the live requests of the bucket's OLDEST staging batch; rows past them
  (a partial batch, an expired or failed request) keep whatever an
  earlier batch left there and reach no response.  The staging batch
  returns to its bucket's free list once its batch is finished, after the
  read-back of that batch's own outputs: jax reads the host buffer after
  ``predict`` has returned.
* A batch runs in two halves.  Its **launch** (``_launch``): queue-wait
  bookkeeping, the hand-over of its staging batch, ``predictor.predict``
  until it returns (on a mask network the batch's pyramid captured) and
  ``copy_to_host_async`` on the outputs.  Its **finish** (``_finish``): the
  read-back, the post-process, the futures, counters, hists, trace spans
  and capture (on a mask network: up to the dispatch of the mask program
  over that batch's own pyramid, the rest in its tail, below), and —
  either way, and only there — the release of the staging batch and,
  unless a tail is pending, of the inflight slot.  A **turn** of the
  dispatcher thread begins with the claim of a due batch, which is
  launched FIRST; then the
  flight the turn before left is finished while the device runs the new
  one.  With nothing due a pending flight is finished at once, inside the
  turn that launched it, so a lone request is answered in the serial order
  and the loop never idles with a batch in flight.  At most TWO batches
  are launched and unfinished at any instant: a constant of the loop, not
  a knob — how often the overlap engages depends only on what the loop
  finds in its queue (counter ``overlapped_turns`` ÷ ``batches``; the sink
  gets the gauge ``serve/in_flight`` at each launch: the batches holding
  an inflight slot, 1 or 2, 3 with a mask network's pending tail).  On a
  mask network the finish ends with the dispatch of the batch's mask
  program, and its read-back, paste and answers are the batch's **tail**,
  finished in the next turn right after the next predict's read-back
  (with nothing due, at once), so the dispatcher never waits for a mask
  program queued behind a predict (:meth:`ServeEngine._dispatch_loop`;
  counter ``deferred_masks`` ÷ ``batches``: how often the tail lands in a
  later turn, 0 on a box network); the batch keeps its inflight slot
  until its tail.  A failure
  in any stage fails that batch's requests only.  ``dispatch_batch``
  (the external dispatcher's surface) is one batch's launch, finish and
  tail back to back.
* Backpressure is a bounded queue: ``submit`` beyond ``max_queue``
  raises :class:`RejectedError` (the frontend's 503) instead of letting
  latency grow without bound.  Per-request deadlines are swept before
  every flush: an expired request fails with
  :class:`DeadlineExceededError` (504) without wasting a forward pass.
* Post-process is the shared ``ops/postprocess`` path — byte-for-byte
  the block ``pred_eval`` runs, so served detections can never drift
  from the eval metric for the same weights.

Telemetry (whatever sink is active): per-request ``serve/queue_wait``
spans; per-batch ``serve/forward`` / ``serve/readback`` /
``serve/postprocess`` spans and ``serve/batch_fill`` / ``serve/pad_ratio``
gauges; ``serve/requests`` / ``serve/batches`` / ``serve/rejected`` /
``serve/shed`` / ``serve/deadline_exceeded`` / ``serve/recompile``
counters.  The same counts are mirrored in :attr:`ServeEngine.counters`
so ``/metrics`` works with telemetry disabled — and likewise the engine
keeps its own latency :class:`~mx_rcnn_tpu.telemetry.Hist` instances
(queue wait / service time / end-to-end request time, plus per-bucket
request time), which is what lets ``serve/controller.py`` read live p99s
and ``/metrics`` expose histogram families in every configuration.

Stages (sink on or off): every stage of a dispatcher turn, of a request
and of start-up is timed ONCE, through :class:`~mx_rcnn_tpu.telemetry.stage`
— a ``Hist`` in :attr:`ServeEngine.hists`, a span in the sink when one is
on, and a ``jax.profiler.TraceAnnotation`` of the same name, so a
``jax.profiler`` trace of the running server shows them on the Python
threads' line beside the device's ops.  Dispatcher thread, flat siblings
with no enclosing annotation: ``serve/idle`` (the wait when nothing is
due and nothing in flight), ``serve/assemble`` (a hand-over: the wait for
a row of the claimed staging batch whose copy is still running on its
caller's thread, at most one row copy; no allocation, no copy),
``serve/forward`` (h2d + enqueue + the d2h started), ``serve/readback``
(what is LEFT of the wait for the device when the finish half gets there,
+ what is left of the d2h: near the program's whole run for a lone batch
or a device-bound turn, a few ms once the host is the slower), per image
``serve/post/decode`` (``decode_image_boxes`` on the real requests' rows:
numpy on the arrays the readback brought, no device array and no program)
/ ``serve/post/nms`` (their ``Hist``s observe once a batch, summed over its
images) and ``serve/post/records`` (on the timeline only);
``serve/postprocess`` (the whole loop) and ``serve/service_time`` are
clocks without an annotation.  ``serve/service_time`` is ONE TURN of the
dispatcher thread — from the claim of a batch to the claim of the next
(its launch + the finish of the flight before it), or to the end of its
own finish when nothing else was due — one observation a claimed batch,
so the turns never overlap and their sum plus ``serve/idle``'s cannot
pass the wall clock; it is NOT a batch's claim → last response (that is
``serve/request_time`` less ``serve/queue_wait``, and the tracer's
``engine/dispatch`` span).  ``serve/h2d`` exists in
``serve_e2e`` mode.  On a mask network (``cfg.network.HAS_MASK``, and only
there) the turn has a second stage after the post-process
(:meth:`ServeEngine._mask_stage`): ``serve/mask`` (the whole of it, a clock
without an annotation: the dispatcher's seconds in the stage, one
observation a batch, never the wall across the two turns it spans) =
``serve/mask/forward`` (the records' boxes and classes filled into one
``(B, MAX_PER_IMAGE)`` pair + the mask program's dispatch over the pyramid
``predict`` left on the device, at the end of the batch's finish) + in its
tail ``serve/mask/readback`` (what is left of the wait for the device + the
d2h of the ``(B, R, 28, 28)`` probabilities: at most one mask program's
run) + per image ``serve/mask/paste`` (paste into the request's raw frame +
RLE; one observation a batch), with the counters
``mask_dispatches``, ``mask_rois`` (live records masked),
``mask_readback_bytes`` and ``mask_native`` (records pasted by the native
call) on ``/metrics``; the futures are set at its end, each record with its
``"segmentation"``, and ``serve/request_time`` and the batch's trace spans
are taken there.  Request threads: ``frontend/read``,
``frontend/decode``, ``serve/host_prep``, ``serve/stage_row`` (the copy of
the prepared image into its staging row, :meth:`ServeEngine._write_row`)
and ``frontend/reply`` (the response's serialisation and write).
Start-up: ``setup/model`` / ``setup/params`` / ``setup/predictor``
(``serve.py::_build_engine``) and ``setup/warmup`` (``warmup()``), kept as
seconds.
``/metrics`` carries ``"stages": {name: {"count", "sum_s", "cpu_s",
"minflt"}}`` for every ``Hist`` (monotone: two scrapes' difference is the
window's; ``cpu_s`` / ``minflt`` are the observing thread's CPU seconds and
minor page faults inside the same instants as ``sum_s`` — a stage's from
:func:`telemetry.thread_usage` at its two ends, ``serve/service_time``'s at
the two claims that bound the turn — and 0 for the hists that are not
clocks of one thread: ``serve/queue_wait``, ``serve/request_time``),
``"host": {"cpu_s", "cores"}`` (the process's CPU seconds, every thread's,
and the cores it may run on: over two scrapes' ``t_s``, the cores it kept
busy), ``"setup":
{"model_s", "params_s", "predictor_s", "warmup_s"}``, the scrape's
``"t_s"`` (the server's own clock, for a rate between two scrapes), the
counters ``post_candidates`` / ``post_kept`` (÷ ``served``: how much the
host post-process is handed an image, and whether ``TEST.MAX_PER_IMAGE``
binds) and ``post_nms_native`` (the images whose per-class NMS was the one
native call of ``ops/postprocess.per_class_nms``: equal to ``served``, or 0
where the library did not build and the Python loop ran),
``assemble_waits`` (rows whose copy was still running
when a turn claimed their batch, and was waited for), ``staging_allocs`` (staging batches ever
allocated: flat after warm-up, like ``recompiles``; the sink also gets
the gauge ``serve/staging_free``), ``overlapped_turns`` (batches
launched while another was in flight) and ``deferred_masks`` (a mask
network's batches whose mask read-back and paste ran in a later turn than
their post-process), on a pyramid
network ``rois_valid`` and ``rois_level_p2`` … ``rois_level_p5`` (the
proposals the joint NMS kept and the level the FPN
paper's eq. 1 pools each from, counted on the host by the legacy path) and
``h2d_bytes`` beside ``readback_bytes`` (÷ ``batches``: what a
turn ships each way, the number ``--serve-e2e`` shrinks), and under
``"compile"`` the registry's snapshot with what XLA really compiled or
loaded (``xla_compiles``, ``xla_compile_s``, ``persistent_cache_hits`` /
``_misses``; ``jaxpr_traces`` rising in steady state is a retrace a turn,
which no compile counter shows).

SLO hooks (driven by :class:`~mx_rcnn_tpu.serve.controller.SLOController`
when ``--target-p99-ms`` is set, inert otherwise):

* per-bucket policy — ``set_bucket_policy(key, max_batch, max_delay_ms)``
  lowers a bucket's flush threshold below ``opts.batch_size`` and/or its
  flush delay below ``opts.max_delay_ms``.  The COMPILED program shape is
  untouched: a smaller ``max_batch`` just flushes earlier and pads more,
  trading fill for head-of-line latency without any recompile.
* admission limit — ``set_admit_limit(n)`` sheds submits (503, counted
  as ``serve/shed``, distinct from queue-full ``serve/rejected``) once
  queue depth reaches ``n`` < ``max_queue``, so the controller can cut
  intake BEFORE the queue trend turns into deadline misses.
"""

from __future__ import annotations

import os
import resource
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from mx_rcnn_tpu import native, telemetry
from mx_rcnn_tpu.telemetry import Hist, tracectx
from mx_rcnn_tpu.telemetry.tracectx import TraceContext
from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.data.image import bucket_shape, stage_raw_to_bucket
from mx_rcnn_tpu.data.loader import prepare_image
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.ops.postprocess import (decode_image_boxes,
                                         detections_to_records,
                                         device_dets_to_per_class,
                                         per_class_nms)


class RejectedError(RuntimeError):
    """Queue full (or engine stopped) — the frontend's 503."""


class DeadlineExceededError(RuntimeError):
    """The request's deadline expired before it was served — 504."""


@dataclass(frozen=True)
class ServeOptions:
    """Engine knobs (CLI: ``--serve-batch`` / ``--max-delay-ms`` /
    ``--max-queue`` / ``--deadline-ms``)."""

    batch_size: int = 4
    # flush a partial batch once its oldest request has waited this long;
    # THE latency/throughput knob (0 = serve singletons immediately)
    max_delay_ms: float = 10.0
    # bounded-queue backpressure: submits beyond this many queued requests
    # (across all buckets) are rejected, not parked
    max_queue: int = 64
    # default per-request deadline (<= 0 disables); requests may override
    deadline_ms: float = 30000.0
    # host prep worker processes (data/workers.py shm pool, CLI
    # --loader-workers): 0 keeps prepare_image on each caller's thread;
    # N > 0 ships it to the shared pool — the serving ingest bottleneck
    # once offered load outruns one interpreter's resize throughput
    prep_workers: int = 0
    # single-dispatch serving (CLI --serve-e2e): submit() only STAGES the
    # raw uint8 into its bucket (data/image.py stage_raw_to_bucket — no
    # resize/normalize on the host), and each batch runs the fused
    # prep → forward → decode+NMS registry program ("serve_e2e"): one
    # h2d transfer, one dispatch, one (B, cap, 6) readback.  Off (the
    # default) reproduces the PR-3 host-prep + host-NMS path
    # byte-for-byte.  Staging always runs on the caller's thread — it is
    # a pad-copy, far below the prep-worker break-even.
    serve_e2e: bool = False

    def __post_init__(self):
        if self.batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {self.batch_size}")
        if self.max_queue < self.batch_size:
            raise ValueError(
                f"max_queue ({self.max_queue}) must be >= batch_size "
                f"({self.batch_size}) or a full batch could never queue")
        if self.prep_workers < 0:
            raise ValueError(
                f"prep_workers must be >= 0, got {self.prep_workers}")


class ServeFuture:
    """Completion handle for one submitted request."""

    __slots__ = ("_event", "_result", "_error", "queue_wait_s",
                 "hardness", "request")

    def __init__(self):
        self._event = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None
        self.queue_wait_s: Optional[float] = None
        # cascade sidecars, set by the on-device gate when a CascadeRouter
        # is attached to the serving engine: the per-image hardness scalar
        # and a backlink to the request (whose staged uint8 buffer an
        # escalation reuses).  None on every non-cascade path.
        self.hardness: Optional[float] = None
        self.request = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> List[dict]:
        """Block for the detections (records sorted by descending score:
        ``{"cls", "score", "bbox": [x1,y1,x2,y2]}`` in ORIGINAL image
        coordinates).  Raises the request's failure if it was rejected,
        expired, or the forward errored."""
        if not self._event.wait(timeout):
            raise TimeoutError("request not served within wait timeout")
        if self._error is not None:
            raise self._error
        return self._result

    def _set_result(self, result):
        self._result = result
        self._event.set()

    def _set_error(self, err: BaseException):
        self._error = err
        self._event.set()


class _Request:
    __slots__ = ("image", "im_info", "t_enqueue", "deadline", "bucket",
                 "future", "raw_hw", "ratio", "orig_hw", "staged",
                 "staged_hw", "stream", "trace", "rid", "staging", "row")

    def __init__(self, image, im_info, t_enqueue, deadline, bucket=None,
                 raw_hw=None, ratio=None, orig_hw=None, staged=None,
                 staged_hw=None, stream=None, trace=None):
        self.image = image          # bucket-padded network input, or (in
        # serve_e2e mode) the STAGED raw uint8 bucket array; the request's
        # own array, never a view into a staging batch.  Dropped once its
        # row is written, except in serve_e2e mode, where capture and the
        # cascade's submit_staged read it after the batch
        self.im_info = im_info
        self.t_enqueue = t_enqueue  # monotonic
        self.deadline = deadline    # monotonic instant or None
        self.bucket = bucket        # (H, W) routing key, for per-bucket obs
        # serve_e2e sidecars (stage_raw_to_bucket): device prep consumes
        # them inside the fused program; None on the legacy path
        self.raw_hw = raw_hw        # (2,) int32 [h, w] of the raw image
        self.ratio = ratio          # () float32 output→input sampling ratio
        # flywheel capture sidecars: pre-staging (h, w) of the submitted
        # image (detections are in those coordinates), plus — legacy path
        # with capture on only — a staged uint8 copy and its valid extent
        # (in e2e mode ``image`` already IS the staged buffer)
        self.orig_hw = orig_hw
        self.staged = staged
        self.staged_hw = staged_hw
        self.stream = stream        # stream_id when submitted via a
        # StreamManager; lets the flush side count cross-stream coalescing
        self.trace = trace          # TraceContext when the request is part
        # of a distributed trace (tracectx); None otherwise
        self.rid = None             # per-engine request id, assigned at
        # flush time ONLY for batches carrying a traced request — the
        # batch-causality key ("my request shared a dispatch with rids X")
        self.staging = None         # the _Staging batch it has a row of,
        self.row = None             # and which (None again: its copy raised)
        self.future = ServeFuture()


class _Staging:
    """One reused host batch of a bucket: the predict program's whole input
    ``(B,) + prepared.shape`` in the prepared image's dtype, and the
    ``(B, 3)`` ``im_info`` beside it.  Rows are handed out in order under
    the engine's lock and written by the threads that prepared the images;
    every field but the arrays' contents is guarded by that lock."""

    __slots__ = ("images", "im_info", "taken", "pending", "claimed",
                 "retired")

    def __init__(self, batch: int, image: np.ndarray, im_info):
        self.images = np.empty((batch,) + image.shape, image.dtype)
        # written once, here: every page resident before a turn reads it
        self.images.fill(0)
        # rows nobody writes still hold a real image's im_info (a zero
        # scale would send infs through the padding rows' boxes)
        self.im_info = np.tile(np.asarray(im_info), (batch, 1))
        self.taken = 0         # rows handed out since it was last opened
        self.pending = 0       # of those, copies that have not finished
        self.claimed = False   # a turn has it: the dispatcher waits on it
        self.retired = False   # out of the line and done with: on the free
        # list, or going there with its last pending copy


class _Flight:
    """One batch between the two halves of its turn: launched — its forward
    enqueued on the device, the d2h of its outputs under way — and not yet
    finished; on a mask network also between its finish and its tail, the
    mask program dispatched and not yet read back (``mask``).  Written by
    :meth:`ServeEngine._launch`, read by :meth:`ServeEngine._finish` and
    :meth:`ServeEngine._finish_tail`, all on the dispatcher's thread."""

    __slots__ = ("reqs", "key", "staging", "t_claim", "usage_claim",
                 "overlapped", "shape", "first", "outs", "feats", "h2d_bytes",
                 "phases", "mask", "xfer", "deferred")

    def __init__(self, reqs: List[_Request], t_claim: float, usage_claim):
        self.reqs = reqs            # the live requests; none: the launch
        # failed them all, and the finish has only the slot to release
        self.key, self.staging = reqs[0].bucket, reqs[0].staging
        self.t_claim = t_claim      # monotonic: taken off the queue
        # the dispatcher thread's (cpu_s, minflt) at that same instant
        self.usage_claim = usage_claim
        self.overlapped = False     # launched while another was in flight
        self.shape = None           # the program's registry key, and
        self.first = False          # whether this is its first dispatch
        self.outs = None            # the forward's outputs, on the device
        self.feats = None           # a mask network: this batch's pyramid
        self.h2d_bytes = 0          # serve_e2e: what the one h2d shipped
        self.phases: Dict[str, float] = {}  # the tracer's phase seconds
        self.mask: Optional[_MaskStage] = None  # dispatched, not read back
        self.xfer: dict = {}        # the finish's counter increments, booked
        # with the batch by its tail
        self.deferred = False       # its tail ran in a later turn


class _MaskStage:
    """A mask network's second stage of one batch, from the dispatch of its
    mask program at the end of the batch's finish
    (:meth:`ServeEngine._mask_stage`) to the read-back, paste and answers in
    its tail (:meth:`ServeEngine._mask_tail`).  ``whole`` is the stage's
    one clock, entered in both, booked once at the tail's end."""

    __slots__ = ("held", "feats", "rows", "R", "shape", "use_native",
                 "whole", "start", "probs", "first", "forward_s", "h2d_bytes")

    def __init__(self, held, feats, rows: int, R: int, shape, use_native):
        self.held = held            # [(request, its record list)]
        self.feats = feats          # the pyramid, while a pass may need it
        self.rows, self.R = rows, R  # a pass fills one (rows, R) pair
        self.shape = shape          # the mask program's registry key
        self.use_native = use_native
        self.whole = telemetry.stage("serve/mask", annotate=False)
        self.start = 0              # the first record of the current pass
        self.probs = None           # the current pass's output, on the device
        self.first = False          # the current pass is the program's first
        self.forward_s = 0.0        # and its serve/mask/forward seconds
        self.h2d_bytes = 0          # one pass's boxes + labels


def _copy_to_host_async(arrays):
    """Start the d2h of a launched batch's outputs, so the transfer too
    overlaps the host's work on the batch before; a stub predictor's numpy
    arrays have nothing to start."""
    for a in arrays:
        start = getattr(a, "copy_to_host_async", None)
        if start is not None:
            start()


def _roi_level_counts(rois: np.ndarray, roi_valid: np.ndarray) -> dict:
    """A batch's valid proposals and how the FPN paper's eq. 1 spreads them
    over P2..P5, as ``models/fpn.py::_assign_level`` writes it (``+1``
    widths, k0 = 4 at 224 px, held to 2..5) — counted on the host from the
    arrays the legacy path reads back anyway.  rois (n, R, 4), roi_valid
    (n, R) → the ``rois_valid`` / ``rois_level_p2`` … ``p5`` increments."""
    valid = np.asarray(roi_valid, bool)
    w = rois[..., 2] - rois[..., 0] + 1.0
    h = rois[..., 3] - rois[..., 1] + 1.0
    k = np.clip(np.floor(4.0 + np.log2(np.sqrt(w * h) / 224.0 + 1e-8)), 2, 5)
    out = {"rois_valid": int(valid.sum())}
    for lvl in (2, 3, 4, 5):
        out[f"rois_level_p{lvl}"] = int(np.count_nonzero(valid & (k == lvl)))
    return out


class ServeEngine:
    """The dynamic batcher.  ``start()`` before submitting; ``stop()``
    fails whatever is still queued (a draining stop would hold clients
    through a full queue's worth of forwards)."""

    def __init__(self, predictor, cfg: Config,
                 options: Optional[ServeOptions] = None):
        self.predictor = predictor
        self.cfg = cfg
        self.opts = options or ServeOptions()
        # serving pins SCALES[0] exactly like the TEST path (TestLoader):
        # one (short, long) pair, two orientation buckets
        self._scale = cfg.tpu.SCALES[0]
        self._queues: Dict[Tuple[int, int], List[_Request]] = {}
        # staging batches (module docstring): per bucket the ones that hold
        # queued requests, oldest first — only the last can have rows left
        # to hand out — and the free ones.  A bucket never has more than
        # _staging_cap (what max_queue can hold, the open one and the two
        # in flight), 103-120 MB each at the benchmark's sizes
        self._staging: Dict[Tuple[int, int], List[_Staging]] = {}
        self._staging_free: Dict[Tuple[int, int], List[_Staging]] = {}
        self._staging_n: Dict[Tuple[int, int], int] = {}
        self._staging_cap = -(-self.opts.max_queue
                              // self.opts.batch_size) + 3
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        # external-dispatch mode (multi-model ModelPool): the engine owns
        # queues, policy, and forwards, but a POOL dispatcher thread calls
        # poll()/dispatch_batch() instead of the engine spawning its own
        # loop — one device owner interleaving several models' buckets
        self._external = False
        # optional work signal for that pool dispatcher: called (with no
        # lock ordering guarantees) whenever new work or a policy change
        # may have made a flush due
        self.on_work = None
        # readiness (distinct from liveness): set by warmup() once every
        # (bucket, batch) program is registered — /readyz gates routing on
        # it while /healthz only proves the process answers
        self._ready = threading.Event()
        # drain mode (weight hot-reload): no NEW admissions, queued work
        # still flushes; _inflight counts batches claimed and not yet
        # finished (at most two, and a mask network's pending tail:
        # _dispatch_loop), so drain() can block until the device is
        # quiescent and every answer is out
        self._draining = False
        self._inflight = 0
        # checkpoint generation serving right now (atomic under _lock;
        # bumped by the hot-reload path, exposed on /metrics and /readyz)
        self.generation = 0
        # program bookkeeping: a real Predictor carries a ProgramRegistry
        # (one key space for trainer/eval/serve, AOT hit/miss accounting
        # against the persistent cache); duck-typed predictors fall back
        # to the original local shape set.  jit caches one program per
        # input shape, so the first dispatch of each bucket shape is the
        # compile either way.
        self.registry = getattr(predictor, "registry", None)
        self._dtype = getattr(predictor, "infer_dtype", "float32")
        self._seen_shapes = set()
        self.counters = {"requests": 0, "served": 0, "batches": 0,
                         "rejected": 0, "shed": 0, "deadline_exceeded": 0,
                         "recompiles": 0, "warmup_programs": 0,
                         f"recompiles_{self._dtype}": 0,
                         # boundary-crossing accounting (the serve_e2e
                         # contract: exactly 1/1/1 per batch; the legacy
                         # path reports its own so bench can compare)
                         "h2d_transfers": 0, "dispatches": 0,
                         "readbacks": 0, "readback_bytes": 0,
                         "h2d_bytes": 0,
                         # host post-process work: boxes over TEST.THRESH
                         # that went into the per-class NMS, records returned
                         "post_candidates": 0, "post_kept": 0,
                         # images whose per-class NMS was the one native
                         # call (== served; 0 = no library, the loop ran)
                         "post_nms_native": 0,
                         # staging batches: rows a turn had to wait for,
                         # batches ever allocated (flat once warm); the rows
                         # written are serve/stage_row's count
                         "assemble_waits": 0, "staging_allocs": 0,
                         # batches launched while another was in flight
                         # (/ batches: how often the overlap engages), and
                         # a mask network's batches whose mask read-back and
                         # paste ran in a later turn than their post-process
                         "overlapped_turns": 0, "deferred_masks": 0,
                         "host_prep_ms_total": 0.0,
                         # stream-aware flush bookkeeping: batches that
                         # carried >= 1 stream frame, the frame count, and
                         # batches mixing frames from DIFFERENT streams
                         # (the cross-stream coalescing win).  Skipped
                         # frames never reach the engine, so the 1/1/1
                         # per-batch contract above is stream-agnostic.
                         "stream_batches": 0, "stream_batch_frames": 0,
                         "stream_coalesced_batches": 0}
        if cfg.network.HAS_FPN:
            # a pyramid network's proposals: how many survived the joint
            # NMS and which level eq. 1 pools each from (_finish_legacy)
            self.counters.update(_roi_level_counts(np.zeros((0, 4)),
                                                   np.zeros((0,), bool)))
        # a mask network's turn has a second stage (_mask_stage); no
        # other network's turn, counters or /metrics know of it
        self._has_mask = bool(cfg.network.HAS_MASK)
        if self._has_mask:
            if self.opts.serve_e2e:
                raise ValueError(
                    "--serve-e2e cannot serve a mask network "
                    "(cfg.network.HAS_MASK): the fused single-dispatch "
                    "path has no mask stage and "
                    "would answer boxes without their masks; drop "
                    "--serve-e2e (the default path serves masks)")
            # mask programs dispatched, live records masked, bytes of
            # probabilities read back, records pasted by the native call
            self.counters.update(mask_dispatches=0, mask_rois=0,
                                 mask_readback_bytes=0, mask_native=0)
        self._pool = None  # prep worker pool (opts.prep_workers > 0)
        # engine-authoritative latency distributions (same contract as
        # self.counters: live even with telemetry off — the controller's
        # and /metrics' source of truth); Hist has its own lock, so these
        # are observed OUTSIDE self._lock
        self.hists: Dict[str, Hist] = {
            "serve/queue_wait": Hist(),
            "serve/service_time": Hist(),
            "serve/request_time": Hist(),
            # per-request host prep/staging wall (submit-side): the cost
            # serve_e2e shrinks from a cv2 resize+normalize to a pad-copy
            "serve/host_prep": Hist(),
        }
        # where a dispatcher turn and a request spend their time
        # (telemetry.stage: each also a span on the profiler's timeline).
        # One observation a batch (serve/post/*: summed over its images),
        # except serve/idle — one an idle period, when it ends — and
        # serve/stage_row and frontend/* — one a request.
        for name in ("serve/idle", "serve/assemble", "serve/forward",
                     "serve/readback", "serve/postprocess",
                     "serve/post/decode", "serve/post/nms",
                     "serve/stage_row", "frontend/read", "frontend/decode",
                     "frontend/reply"):
            self.hists[name] = Hist()
        if self.opts.serve_e2e:
            self.hists["serve/h2d"] = Hist()
        if self._has_mask:
            for name in ("serve/mask", "serve/mask/forward",
                         "serve/mask/readback", "serve/mask/paste"):
                self.hists[name] = Hist()
        # start-up's split, seconds ("model_s", "params_s", "predictor_s",
        # "warmup_s"): written once by serve.py's _build_engine and warmup()
        self.setup: Dict[str, float] = {}
        self._bucket_hists: Dict[str, Hist] = {}  # "HxW" -> request_time
        # SLO-controller policy overrides (None/absent = configured opts);
        # max_batch is a FLUSH THRESHOLD <= opts.batch_size — the padded
        # program shape never changes, so no recompiles
        self._bucket_batch: Dict[Tuple[int, int], int] = {}
        self._bucket_delay_ms: Dict[Tuple[int, int], float] = {}
        self._admit_limit: Optional[int] = None
        self.controller = None  # set by SLOController.start()
        # flywheel request capture: NULL sink unless a capture dir was
        # configured (serve.py --capture-dir attaches a RequestCapture).
        # Same contract as telemetry — capture-off costs one attribute
        # check per batch, and the NULL sink raises if recorded into.
        from mx_rcnn_tpu.flywheel.capture import NULL_CAPTURE
        self.capture = NULL_CAPTURE
        # distributed-tracing rid counter (see _Request.rid); only
        # advanced when tracing is enabled AND a batch carries a trace
        self._next_rid = 0
        # StreamManager attaches itself here; /metrics grows a "stream"
        # section when set.  The engine never calls into it — streaming
        # stays a layer above the batcher.
        self.stream = None
        # CascadeRouter attaches itself here (on the SMALL model's engine
        # only): each serve_e2e batch then folds its on-device detections
        # into per-image hardness before readback.  Cascade-off costs
        # exactly this one attribute check per batch — the capture /
        # telemetry contract.
        self.cascade = None

    # -- lifecycle -------------------------------------------------------

    def start(self, external: bool = False) -> "ServeEngine":
        """Spawn the dispatcher thread — or, with ``external=True``
        (multi-model pool mode), skip it: the engine is fully live for
        submits/policy/metrics but batches only flush when an external
        dispatcher calls :meth:`poll` + :meth:`dispatch_batch`."""
        assert self._thread is None and not self._external, \
            "engine already started"
        if self.opts.prep_workers > 0 and self._pool is None:
            from mx_rcnn_tpu.data.workers import WorkerPool

            # image-only pool (no roidb): submit() ships raw frames in,
            # prepared bucket arrays come back through the shm ring
            self._pool = WorkerPool(self.cfg,
                                    num_workers=self.opts.prep_workers)
        if external:
            self._external = True
            return self
        self._thread = threading.Thread(target=self._dispatch_loop,
                                        name="serve-dispatch", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0):
        if self._pool is not None:
            self._pool.close(timeout=timeout)
            self._pool = None
        with self._cond:
            self._stop = True
            pending = [r for q in self._queues.values() for r in q]
            for q in self._queues.values():
                q.clear()
            self._staging.clear()
            self._staging_free.clear()
            self._staging_n.clear()
            self._cond.notify_all()
        for r in pending:
            r.future._set_error(RejectedError("engine stopped"))
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None
        self._external = False
        if self.on_work is not None:
            self.on_work()
        if self.capture.enabled:
            self.capture.close()

    # -- readiness / drain (replica supervision + hot reload) ------------

    def mark_ready(self):
        """Warmup's signal: every steady-state program is registered.
        Flips ``/readyz`` to 200 (once per process unless a drain is in
        progress)."""
        self._ready.set()

    def is_ready(self) -> bool:
        with self._lock:
            return (self._ready.is_set() and not self._draining
                    and not self._stop
                    and (self._thread is not None or self._external))

    def readiness(self) -> dict:
        """The ``/readyz`` payload — warmup + admission state, distinct
        from ``/healthz`` liveness (a warming or draining replica is alive
        but must not receive routed traffic)."""
        with self._lock:
            return {
                "ready": (self._ready.is_set() and not self._draining
                          and not self._stop
                          and (self._thread is not None or self._external)),
                "warmed": self._ready.is_set(),
                "draining": self._draining,
                "generation": self.generation,
                "queue_depth": sum(len(q) for q in self._queues.values()),
                "admit_limit": self._admit_limit,
            }

    def drain(self, timeout: float = 30.0) -> bool:
        """Stop admitting (503) and block until every queued request has
        flushed and no batch is on the device — the quiescent point a
        weight swap needs.  Returns False if the queue didn't empty within
        ``timeout`` (caller should resume() and retry later)."""
        deadline = time.monotonic() + timeout
        with self._cond:
            self._draining = True
            self._cond.notify_all()
            while (any(self._queues.values()) or self._inflight):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(timeout=min(remaining, 0.05))
        return True

    def resume(self):
        """Re-open admissions after a drain()."""
        with self._cond:
            self._draining = False
            self._cond.notify_all()
        if self.on_work is not None:
            self.on_work()

    # -- intake ----------------------------------------------------------

    def bucket_key(self, h: int, w: int) -> Tuple[int, int]:
        """The static padded (H, W) bucket a raw (h, w) image routes to —
        orientation picks the compiled program, exactly like the loaders'
        aspect grouping."""
        return bucket_shape(self._scale,
                            max(self.cfg.network.IMAGE_STRIDE,
                                self.cfg.network.RPN_FEAT_STRIDE),
                            landscape=(w >= h))

    def queue_depth(self) -> int:
        with self._lock:
            return sum(len(q) for q in self._queues.values())

    # -- SLO-controller policy surface -----------------------------------

    def bucket_policy(self, key: Tuple[int, int]) -> Tuple[int, float]:
        """Effective (flush_batch, max_delay_ms) for a bucket — configured
        opts unless the controller has tightened them."""
        with self._lock:
            return (self._bucket_batch.get(key, self.opts.batch_size),
                    self._bucket_delay_ms.get(key, self.opts.max_delay_ms))

    def set_bucket_policy(self, key: Tuple[int, int],
                          max_batch: Optional[int] = None,
                          max_delay_ms: Optional[float] = None):
        """Override a bucket's flush threshold / delay.  ``max_batch`` is
        clamped to [1, opts.batch_size] — the compiled shape is fixed, the
        knob only flushes earlier.  ``None`` leaves a knob unchanged;
        setting the configured value drops the override."""
        with self._cond:
            if max_batch is not None:
                b = max(1, min(int(max_batch), self.opts.batch_size))
                if b == self.opts.batch_size:
                    self._bucket_batch.pop(key, None)
                else:
                    self._bucket_batch[key] = b
            if max_delay_ms is not None:
                d = max(0.0, float(max_delay_ms))
                if d == self.opts.max_delay_ms:
                    self._bucket_delay_ms.pop(key, None)
                else:
                    self._bucket_delay_ms[key] = d
            # a shorter delay may make a parked bucket due immediately
            self._cond.notify()
        if self.on_work is not None:
            self.on_work()

    def set_admit_limit(self, limit: Optional[int]):
        """Shed submits (503) at this queue depth — the controller's
        early-shed valve.  ``None`` restores plain max_queue backpressure."""
        with self._lock:
            self._admit_limit = (None if limit is None
                                 else max(1, min(int(limit),
                                                 self.opts.max_queue)))

    def known_buckets(self) -> List[Tuple[int, int]]:
        """Buckets that have ever queued a request (adaptation targets)."""
        with self._lock:
            return sorted(self._queues.keys())

    def latency_hists(self) -> Dict[str, Hist]:
        """Engine-authoritative latency histograms, global + per-bucket
        (``serve/request_time/HxW``).  The engine lock only guards the
        dict copy; Hist contents are internally locked."""
        out = dict(self.hists)
        with self._lock:
            bucket = dict(self._bucket_hists)
        out.update({f"serve/request_time/{k}": h for k, h in bucket.items()})
        return out

    def policy(self) -> Dict[str, dict]:
        """Live effective policy per known bucket (for /metrics)."""
        with self._lock:
            keys = sorted(self._queues.keys())
            out = {}
            for key in keys:
                out[f"{key[0]}x{key[1]}"] = {
                    "max_batch": self._bucket_batch.get(
                        key, self.opts.batch_size),
                    "max_delay_ms": self._bucket_delay_ms.get(
                        key, self.opts.max_delay_ms),
                }
            return out

    def submit(self, image: np.ndarray,
               deadline_ms: Optional[float] = None,
               stream: Optional[str] = None,
               trace: Optional[TraceContext] = None) -> ServeFuture:
        """Enqueue one raw RGB HWC image (uint8 or float).  Returns a
        :class:`ServeFuture`; raises :class:`RejectedError` immediately
        when the queue is full or the engine is stopped.  ``stream`` tags
        the request with its originating stream_id (StreamManager) so the
        flush side can account cross-stream batch sharing — it changes
        nothing about routing, batching, or the forward.  ``trace``
        (a :class:`~mx_rcnn_tpu.telemetry.tracectx.TraceContext`) rides
        the request so the flush side can emit batch-causality spans —
        equally inert for routing and batching."""
        if image.ndim != 3 or image.shape[2] != 3:
            raise ValueError(f"expected (H, W, 3) RGB image, "
                             f"got shape {tuple(image.shape)}")
        tel = telemetry.get()
        raw_hw = ratio = None
        with self._stage("serve/host_prep") as prep:
            if self.opts.serve_e2e:
                # single-dispatch mode: no host resize/normalize — stage
                # the raw uint8 into its bucket (pad-copy; oversized raws
                # shrink host-side, see stage_raw_to_bucket) and let the
                # fused program run the prep on device
                prepared, raw_hw, ratio, im_info = stage_raw_to_bucket(
                    np.asarray(image), self._scale,
                    max(self.cfg.network.IMAGE_STRIDE,
                        self.cfg.network.RPN_FEAT_STRIDE))
            elif self._pool is not None:
                # host prep off the dispatcher thread either way: on the
                # caller's thread (workers=0 — concurrent frontends
                # parallelize the resize) or in the shared prep worker pool
                # (byte-identical transform, pinned by test_loader_workers),
                # so the device hot path never waits on a resize
                prepared, im_info = self._pool.prepare(np.asarray(image),
                                                       self._scale)
            else:
                prepared, im_info = prepare_image(np.asarray(image),
                                                  self.cfg, self._scale)
        prep_s = prep.seconds
        tel.observe("serve/host_prep", prep_s)
        orig_hw = (int(image.shape[0]), int(image.shape[1]))
        staged = staged_hw = None
        if self.capture.enabled and not self.opts.serve_e2e:
            # capture-on, legacy path: also stage the raw uint8 so the
            # flywheel logs the pixels the PII-free contract allows (the
            # e2e path's ``prepared`` already IS that buffer).  Runs on
            # the caller's thread, like the prep itself.
            raw8 = np.asarray(image)
            if raw8.dtype != np.uint8:
                raw8 = np.clip(raw8, 0, 255).astype(np.uint8)
            staged, staged_hw, _, _ = stage_raw_to_bucket(
                raw8, self._scale,
                max(self.cfg.network.IMAGE_STRIDE,
                    self.cfg.network.RPN_FEAT_STRIDE))
        # route on the LOGICAL bucket (pre-s2d padded shape) — under
        # HOST_S2D the prepared array is (H/2, W/2, 12), but orientation
        # and program identity are the bucket's, and /metrics should name
        # buckets in image coordinates
        key = self.bucket_key(image.shape[0], image.shape[1])
        now = time.monotonic()
        if deadline_ms is None:
            deadline_ms = self.opts.deadline_ms
        deadline = now + deadline_ms / 1e3 if deadline_ms > 0 else None
        req = _Request(prepared, im_info, now, deadline, bucket=key,
                       raw_hw=raw_hw, ratio=ratio, orig_hw=orig_hw,
                       staged=staged, staged_hw=staged_hw, stream=stream,
                       trace=trace)
        return self._enqueue(req, key, tel, prep_s=prep_s)

    def _enqueue(self, req: _Request, key, tel,
                 prep_s: float = 0.0) -> ServeFuture:
        """Shared admission tail of :meth:`submit` / :meth:`submit_staged`:
        backpressure + shed checks under the lock, queue insert with the
        next free row of the bucket's open staging batch, counters; then,
        outside the lock and on this caller's thread, the copy of the image
        into that row; work signal."""
        with self._cond:
            if self._stop:
                self.counters["rejected"] += 1
                tel.counter("serve/rejected")
                raise RejectedError("engine stopped")
            if self._draining:
                # weight swap in progress: queued work still flushes but
                # nothing new is admitted — the router retries on an
                # alternate replica, a bare client backs off briefly
                self.counters["rejected"] += 1
                tel.counter("serve/rejected")
                raise RejectedError(
                    "draining (weight swap in progress) — retry shortly")
            depth = sum(len(q) for q in self._queues.values())
            if self._admit_limit is not None and depth >= self._admit_limit:
                # controller-driven early shed: the queue is NOT full, but
                # its trend predicts deadline misses — refuse now, cheaply,
                # instead of serving a 504 after a wasted queue residence
                self.counters["shed"] += 1
                tel.counter("serve/shed")
                raise RejectedError(
                    f"load shed: SLO controller capped admissions at "
                    f"{self._admit_limit} queued requests ({depth} "
                    f"pending) — retry with backoff")
            if depth >= self.opts.max_queue:
                self.counters["rejected"] += 1
                tel.counter("serve/rejected")
                raise RejectedError(
                    f"queue full ({depth}/{self.opts.max_queue} requests "
                    f"pending) — retry with backoff")
            if not self._take_row_locked(req, key, tel):
                # only rows that expired requests left behind get here:
                # live requests alone cannot fill _staging_cap batches
                self.counters["rejected"] += 1
                tel.counter("serve/rejected")
                raise RejectedError(
                    f"queue full ({depth}/{self.opts.max_queue} requests "
                    f"pending, every staging row of bucket {key} taken) "
                    f"— retry with backoff")
            self._queues.setdefault(key, []).append(req)
            self.counters["requests"] += 1
            self.counters["host_prep_ms_total"] += prep_s * 1e3
            tel.counter("serve/requests")
            tel.gauge("serve/queue_depth", depth + 1)
            self._cond.notify()
        self._write_row(req, key)
        if self.on_work is not None:
            self.on_work()
        return req.future

    def _take_row_locked(self, req: _Request, key, tel) -> bool:
        """Give ``req`` the next free row of its bucket's open staging
        batch, opening one (from the free list, else allocated) when there
        is none or it is full.  False: the bucket is at ``_staging_cap``."""
        B = self.opts.batch_size
        line = self._staging.setdefault(key, [])
        if not line or line[-1].taken == B:
            free = self._staging_free.setdefault(key, [])
            if not free:
                n = self._staging_n.get(key, 0)
                if n >= self._staging_cap:
                    return False
                # a bucket is born with the four a saturated engine turns
                # over (two in flight, one waiting full, one filling: born
                # with three, the closed pyramid cell took a fourth in every
                # run, once inside its window; chip runs, PR 33), during
                # warm-up; a deeper queue adds one at a time, on its
                # caller's thread
                more = 1 if n else min(4, self._staging_cap)
                free.extend(_Staging(B, req.image, req.im_info)
                            for _ in range(more))
                self._staging_n[key] = n + more
                self.counters["staging_allocs"] += more
                tel.counter("serve/staging_allocs", more)
            s = free.pop()
            s.taken = 0
            s.claimed = s.retired = False
            line.append(s)
        s = line[-1]
        req.staging, req.row = s, s.taken
        s.taken += 1
        s.pending += 1
        return True

    def _write_row(self, req: _Request, key):
        """The caller's half of batch assembly: copy the prepared image and
        its ``im_info`` into the request's row, outside the lock (numpy
        lets go of the GIL for the copy).  A copy that raises fails its own
        request — out of the queue, its row a padding row — and re-raises,
        so the dispatcher never waits for a row nobody will write.  The
        whole of it is the stage ``serve/stage_row`` (one observation a
        request, a failed copy's too)."""
        s, row = req.staging, req.row
        with self._stage("serve/stage_row"):
            try:
                np.copyto(s.images[row], req.image)
                s.im_info[row] = req.im_info
            except BaseException as e:
                req.row = None
                req.future._set_error(e)
                raise
            finally:
                with self._cond:
                    s.pending -= 1
                    if req.row is None:
                        q = self._queues.get(key, [])
                        if req in q:     # not yet claimed by a turn
                            q.remove(req)
                    elif not self.opts.serve_e2e:
                        req.image = None
                    if not s.pending:
                        if s.retired:
                            self._retire_locked(key, s)
                        elif s.claimed:
                            self._cond.notify_all()

    def _retire_locked(self, key, s: _Staging):
        """``s`` has left its bucket's line and no turn needs it (any
        more): to the free list once no copy is running into it — now, or
        from :meth:`_write_row` with the last of them."""
        s.retired = True
        if not s.pending and not self._stop:
            self._staging_free.setdefault(key, []).append(s)

    def _retire_dead_locked(self, key):
        """Staging batches at the head of a bucket's line that no queued
        request is left in (swept by their deadlines) leave it: to the free
        list now, or with the last copy still running into them."""
        q, line = self._queues.get(key), self._staging.get(key, [])
        head = q[0].staging if q else None
        while line and line[0] is not head:
            if head is None and line[0].taken < self.opts.batch_size:
                break   # the open one: its remaining rows are still good
            self._retire_locked(key, line.pop(0))

    def submit_staged(self, staged: np.ndarray, raw_hw, ratio, im_info,
                      orig_hw,
                      deadline_ms: Optional[float] = None,
                      stream: Optional[str] = None,
                      trace: Optional[TraceContext] = None) -> ServeFuture:
        """Cascade escalation intake: enqueue an ALREADY-STAGED uint8
        bucket buffer (another engine's serve_e2e ``_Request.image``) with
        its staging sidecars, skipping ``stage_raw_to_bucket`` entirely —
        the escalated request reuses the staged pixels byte-for-byte and
        pays zero host prep.  serve_e2e mode only.  The CascadeRouter
        verified at construction that both cascade engines share bucket
        geometry; the shape is re-checked here so a config drift fails
        loudly instead of silently compiling a foreign shape."""
        if not self.opts.serve_e2e:
            raise RejectedError(
                "submit_staged requires serve_e2e mode (staged uint8 "
                "buffers are only a program input on the fused path)")
        key = self.bucket_key(int(orig_hw[0]), int(orig_hw[1]))
        if tuple(staged.shape[:2]) != key:
            raise ValueError(
                f"staged buffer {tuple(staged.shape[:2])} does not match "
                f"this engine's bucket {key} — cascade models must share "
                f"bucket geometry (SCALES + strides)")
        tel = telemetry.get()
        now = time.monotonic()
        if deadline_ms is None:
            deadline_ms = self.opts.deadline_ms
        deadline = now + deadline_ms / 1e3 if deadline_ms > 0 else None
        req = _Request(staged, im_info, now, deadline, bucket=key,
                       raw_hw=raw_hw, ratio=ratio,
                       orig_hw=(int(orig_hw[0]), int(orig_hw[1])),
                       stream=stream, trace=trace)
        return self._enqueue(req, key, tel)

    def predict(self, image: np.ndarray,
                deadline_ms: Optional[float] = None,
                timeout: Optional[float] = 60.0) -> List[dict]:
        """Synchronous convenience: ``submit`` + wait."""
        return self.submit(image, deadline_ms=deadline_ms).result(timeout)

    # -- dispatch --------------------------------------------------------

    def _sweep_expired_locked(self, now: float) -> List[_Request]:
        """Requests past their deadline leave their queues; each leaves
        its row behind as a padding row of its staging batch."""
        expired = []
        for key, q in self._queues.items():
            live = []
            for r in q:
                (expired if r.deadline is not None and r.deadline <= now
                 else live).append(r)
            if len(live) < len(q):
                q[:] = live
                self._retire_dead_locked(key)
        return expired

    def _next_batch_locked(self, now: float):
        """(requests, None) when a bucket is due, else (None, wait_s).

        Full buckets flush first; among due buckets the one whose
        head-of-line request is OLDEST wins — deadline-ordered flushing,
        so no bucket's traffic can starve another's latency budget.

        "Full" and "due" are judged per bucket against the controller's
        policy overrides (flush threshold <= opts.batch_size, possibly
        shortened delay); without a controller both fall back to opts.

        The batch of a due bucket is the queued requests of its OLDEST
        staging batch — the head of the queue and whoever follows it in the
        same one — and claiming it takes that staging batch out of the
        line, so later arrivals open the next."""
        best_key, best_t, best_full = None, None, False
        wait = None
        for key, q in self._queues.items():
            if not q:
                continue
            B = self._bucket_batch.get(key, self.opts.batch_size)
            delay = self._bucket_delay_ms.get(
                key, self.opts.max_delay_ms) / 1e3
            head_t = q[0].t_enqueue
            full = len(q) >= B
            if not (full or (now - head_t) >= delay):
                remaining = delay - (now - head_t)
                wait = remaining if wait is None else min(wait, remaining)
                continue
            # full beats partial; among equals the oldest head wins
            if best_key is None or (full, -head_t) > (best_full, -best_t):
                best_key, best_t, best_full = key, head_t, full
        if best_key is not None:
            q = self._queues[best_key]
            self._retire_dead_locked(best_key)
            s = self._staging[best_key].pop(0)
            s.claimed = True
            n = 1
            while n < len(q) and q[n].staging is s:
                n += 1
            take, q[:] = q[:n], q[n:]
            return take, None
        return None, wait

    def _fail_expired(self, expired: List[_Request]):
        for r in expired:
            self.counters["deadline_exceeded"] += 1
            telemetry.get().counter("serve/deadline_exceeded")
            r.future._set_error(DeadlineExceededError(
                "request expired before it reached a batch (engine "
                "overloaded? raise --max-queue workers or add "
                "replicas)"))

    # -- external (pool) dispatch surface --------------------------------

    def due_state(self, now: float):
        """Lock-held peek for the ModelPool scheduler: ``(due, depth,
        wait_s)``.  ``due`` is True when a bucket would flush right now
        (full, delay elapsed) OR an expired request needs sweeping;
        ``wait_s`` is the earliest instant that could change (None when
        idle).  Purely advisory — :meth:`poll` re-judges under the lock,
        so a racing submit is at worst a missed wakeup until on_work."""
        with self._lock:
            depth = 0
            due = False
            wait = None
            for key, q in self._queues.items():
                depth += len(q)
                if not q or due:
                    continue
                B = self._bucket_batch.get(key, self.opts.batch_size)
                delay = self._bucket_delay_ms.get(
                    key, self.opts.max_delay_ms) / 1e3
                head_t = q[0].t_enqueue
                if len(q) >= B or (now - head_t) >= delay:
                    due = True
                    continue
                remaining = delay - (now - head_t)
                wait = remaining if wait is None else min(wait, remaining)
                for r in q:
                    if r.deadline is not None:
                        if r.deadline <= now:
                            due = True
                            break
                        wait = min(wait, r.deadline - now)
        return due, depth, wait

    def poll(self, now: Optional[float] = None):
        """Claim the next due batch for an external dispatcher: sweeps
        expired requests (failing them with 504) and pops one bucket's
        flush if due.  Returns ``(batch, wait_s)`` — a claimed batch
        holds an inflight slot until :meth:`dispatch_batch` releases it;
        ``(None, wait_s)`` means nothing is due for ``wait_s`` seconds
        (None = idle/stopped)."""
        with self._cond:
            if self._stop:
                return None, None
            expired, batch, wait = self._claim_locked(now)
        self._fail_expired(expired)
        return batch, wait

    def dispatch_batch(self, batch: List[_Request]):
        """Run one batch claimed by :meth:`poll` (the external
        dispatcher's surface): its launch and its finish back to back, one
        turn — on a mask network its tail too.  Fails the batch on error,
        and releases the inflight slot and the batch's staging batch either
        way (:meth:`_finish`)."""
        self._finish(self._launch(batch, time.monotonic(),
                                  telemetry.thread_usage()), ends_turn=True)

    def _claim_locked(self, now: Optional[float] = None):
        """``(expired, batch, wait_s)`` as of ``now``: sweeps the deadlines
        and pops one due bucket's flush, which takes an inflight slot."""
        if now is None:
            now = time.monotonic()
        expired = self._sweep_expired_locked(now)
        batch, wait = self._next_batch_locked(now)
        if batch is not None:
            self._inflight += 1
        return expired, batch, wait

    def _dispatch_loop(self):
        """A turn begins with the claim of a due batch, which is launched
        FIRST; then the flight the turn before left is finished, while the
        device runs the one just launched.  With nothing due, a pending
        flight is finished at once, inside the turn that launched it: the
        serial order falls out whenever the queue holds nothing else, and
        the loop never idles with a batch in flight.  So at most two
        batches are launched and unfinished at any instant — the one
        being finished and the one launched at this turn's top.

        On a mask network a batch has a third stage, its **tail**: the
        finish of batch k ends with the dispatch of its mask program M(k),
        and M(k)'s read-back, the paste, the answers and the booking of k
        run in the next turn, right after the read-back of predict P(k+1).
        A turn is then: claim k+1 → launch P(k+1) → read back P(k) → the
        tail of k−1 → post-process k → dispatch M(k), and the device runs
        P(k), M(k−1), P(k+1), M(k), P(k+2), …  M(k−1) was dispatched after
        P(k) and before P(k+1), so when its read-back begins every predict
        ahead of it on the device has been read back: the dispatcher waits
        for at most one mask program, never for a predict (the invariant).
        With nothing due the pending tail is finished at once with its
        flight, so the loop never idles with a tail pending either; a
        batch keeps its inflight slot until its tail (``_inflight`` may
        read three at a launch), and its staging batch goes back at the
        end of its finish."""
        flight = None   # launched by the turn under way, not finished yet
        tail = None     # finished up to its mask program's dispatch
        while True:
            with self._cond:
                expired, batch, wait = (([], None, None) if self._stop
                                        else self._claim_locked())
                # a pending tail always has a pending flight after it
                if flight is None and batch is None and not expired:
                    if self._stop:
                        return
                    # nothing is due and nothing in flight: the device
                    # idles for want of work, not because the host is
                    # slow.  ONE span a period, however often a submit
                    # wakes the wait, or the timeline would show an idle
                    # period in pieces
                    with self._stage("serve/idle"):
                        while batch is None and not expired:
                            self._cond.wait(timeout=wait)
                            if self._stop:
                                return
                            expired, batch, wait = self._claim_locked()
            self._fail_expired(expired)
            launched = None
            if batch is not None:
                now, usage = time.monotonic(), telemetry.thread_usage()
                if flight is not None:
                    # the turn that launched it ends where this one begins
                    self._book_turn(flight, now, usage)
                launched = self._launch(batch, now, usage)
            if flight is not None:
                tail = self._finish(flight, ends_turn=launched is None,
                                    tail=tail)
            flight = launched

    def _book_turn(self, flight: _Flight, now: float, usage):
        """The turn that claimed ``flight`` is over at ``now``, when the
        dispatcher thread's :func:`telemetry.thread_usage` read ``usage``:
        ``serve/service_time`` (never a batch's claim → its last response,
        which would count the seconds two flights share twice), with the
        CPU seconds and page faults of the thread between the two claims."""
        seconds = now - flight.t_claim
        self.hists["serve/service_time"].observe(
            seconds, cpu_s=usage[0] - flight.usage_claim[0],
            minflt=usage[1] - flight.usage_claim[1])
        telemetry.get().observe("serve/service_time", seconds)

    def _launch(self, reqs: List[_Request], now: float, usage) -> _Flight:
        """The first half of a batch: queue-wait bookkeeping, the
        hand-over of its staging batch (``serve/assemble``), and the
        forward path's own launch up to ``predict`` having returned and the
        d2h of its outputs being under way.  Never raises: a failure fails
        this batch's requests and leaves a flight without requests, which
        :meth:`_finish` only releases."""
        flight = _Flight(reqs, now, usage)
        try:
            self._launch_batch(flight)
        except BaseException as e:  # noqa: BLE001 — fail the batch
            logger.exception("serve batch failed in its launch")
            for r in reqs:
                r.future._set_error(e)
            flight.reqs = []
        return flight

    def _launch_batch(self, flight: _Flight):
        tel = telemetry.get()
        B = self.opts.batch_size
        now, staging = flight.t_claim, flight.staging
        for r in flight.reqs:
            r.future.queue_wait_s = now - r.t_enqueue
            tel.add("serve/queue_wait", now - r.t_enqueue)
            self.hists["serve/queue_wait"].observe(now - r.t_enqueue)
            tel.observe("serve/queue_wait", now - r.t_enqueue)
        # the batch was assembled by its callers, row by row: wait for the
        # copies still running (a request of the last instant; claimed, the
        # staging batch hands out no more rows) and take the whole array.
        # Rows past the live ones keep what an earlier batch left there;
        # they never reach a response
        with self._stage("serve/assemble"):
            with self._cond:
                waits = staging.pending
                while staging.pending:
                    self._cond.wait()
                self.counters["assemble_waits"] += waits
                # this batch and, in the loop, the flight it overlaps
                in_flight = self._inflight
            # a caller whose copy raised has failed its own request
            flight.reqs = [r for r in flight.reqs if r.row is not None]
        if waits:
            tel.counter("serve/assemble_waits", waits)
        if not flight.reqs:
            return
        flight.overlapped = in_flight > 1
        tel.gauge("serve/in_flight", in_flight)
        tel.gauge("serve/batch_fill", len(flight.reqs) / B)
        tel.gauge("serve/pad_ratio", (B - len(flight.reqs)) / B)
        if self.opts.serve_e2e:
            self._launch_e2e(flight, tel)
        else:
            self._launch_legacy(flight, tel)

    def _finish(self, flight: _Flight, ends_turn: bool,
                tail: Optional[_Flight] = None) -> Optional[_Flight]:
        """The second half of a batch: the read-back of its outputs, the
        post-process, the futures, then the hists, counters, trace spans
        and capture.  On a mask network the finish ends instead with the
        dispatch of the mask program over this batch's own pyramid, and
        the rest — the mask read-back, the paste, the futures and the
        booking — is the batch's tail (:meth:`_finish_tail`): finished
        here at once when ``ends_turn``, else returned, for the next turn's
        finish to run as its ``tail`` right after its own read-back
        (:meth:`_dispatch_loop` says why there).  A failure fails this
        batch's requests only; a ``tail`` this finish did not reach is
        finished on the way out.  Either way the staging batch is
        released here, and only here — after the read-back of this batch's
        own outputs has returned: jax reads the host buffer after
        ``predict`` has returned (and ``_launch_e2e``'s ``device_put``
        arrays may alias it until they go), so a staging batch rewritten
        earlier would corrupt the batch in flight; the mask program reads
        no image.  The
        inflight slot goes with the batch's last stage, here or at its
        tail's end.  ``ends_turn``: no other flight is pending, so the turn
        that launched this one ends with it, and is booked before the slot
        goes (``drain`` returning means every clock is booked)."""
        try:
            if flight.reqs:
                self._finish_batch(flight, tail)
        except BaseException as e:  # noqa: BLE001 — fail the batch
            logger.exception("serve batch failed")
            flight.mask = None
            for r in flight.reqs:
                r.future._set_error(e)
        finally:
            if tail is not None and tail.mask is not None:
                self._finish_tail(tail)
            deferred = flight.mask is not None
            if ends_turn and not deferred:
                self._book_turn(flight, time.monotonic(),
                                telemetry.thread_usage())
            with self._cond:
                if not deferred:
                    self._inflight -= 1
                self._retire_locked(flight.key, flight.staging)
                free = len(self._staging_free.get(flight.key, ()))
                self._cond.notify_all()  # drain() waits on this
            telemetry.get().gauge("serve/staging_free", free)
        if not deferred:
            return None
        if ends_turn:
            self._finish_tail(flight, ends_turn=True)
            return None
        flight.deferred = True
        return flight

    def _finish_tail(self, flight: _Flight, ends_turn: bool = False):
        """The third stage of a mask network's batch: its mask read-back,
        paste and answers (:meth:`_mask_tail`), then its booking.  Never
        raises: a failure fails this batch's requests only.  Releases the
        batch's inflight slot, after booking its turn when ``ends_turn``."""
        m, flight.mask = flight.mask, None
        try:
            xfer, flight.phases["mask"] = self._mask_tail(m, telemetry.get())
            for k, v in flight.xfer.items():
                xfer[k] = xfer.get(k, 0) + v
            self._book_batch(flight, xfer)
        except BaseException as e:  # noqa: BLE001 — fail the batch
            logger.exception("serve batch failed in its mask stage")
            for r in flight.reqs:
                r.future._set_error(e)
        finally:
            if ends_turn:
                self._book_turn(flight, time.monotonic(),
                                telemetry.thread_usage())
            with self._cond:
                self._inflight -= 1
                self._cond.notify_all()  # drain() waits on this

    def _finish_batch(self, flight: _Flight, tail: Optional[_Flight]):
        tel = telemetry.get()
        if self.opts.serve_e2e:
            xfer = self._finish_e2e(flight, tel)
        else:
            xfer = self._finish_legacy(flight, tel, tail)
        if flight.mask is not None:
            flight.xfer = xfer      # booked with the batch, by its tail
        else:
            self._book_batch(flight, xfer)

    def _book_batch(self, flight: _Flight, xfer: dict):
        """A batch answered: its request times, counters, trace spans and
        capture, from its own instants, whatever else the turn did."""
        tel = telemetry.get()
        B = self.opts.batch_size
        reqs, now = flight.reqs, flight.t_claim
        # each stage is timed once (telemetry.stage); the batch's phase
        # seconds come back for the tracer, which costs exactly ONE
        # attribute check per batch when tracing is off (the capture
        # contract)
        tracer = tracectx.get()
        # end-to-end request time once per request (global + per-bucket
        # family) — into the engine's own Hists AND the active sink, so
        # the SLO controller and /metrics see them regardless of telemetry
        # config
        done = time.monotonic()
        new_bucket_hists = {}
        for r in reqs:
            req_s = done - r.t_enqueue
            self.hists["serve/request_time"].observe(req_s)
            tel.observe("serve/request_time", req_s)
            if r.bucket is not None:
                bk = f"{r.bucket[0]}x{r.bucket[1]}"
                h = self._bucket_hists.get(bk) or new_bucket_hists.get(bk)
                if h is None:
                    h = new_bucket_hists[bk] = Hist()
                h.observe(req_s)
                tel.observe(f"serve/request_time/{bk}", req_s)
        stream_ids = {r.stream for r in reqs if r.stream is not None}
        stream_frames = sum(1 for r in reqs if r.stream is not None)
        with self._lock:
            self._bucket_hists.update(new_bucket_hists)
            self.counters["batches"] += 1
            self.counters["served"] += len(reqs)
            self.counters["overlapped_turns"] += flight.overlapped
            self.counters["deferred_masks"] += flight.deferred
            if stream_frames:
                self.counters["stream_batches"] += 1
                self.counters["stream_batch_frames"] += stream_frames
                if len(stream_ids) > 1:
                    self.counters["stream_coalesced_batches"] += 1
            for k, v in xfer.items():
                self.counters[k] = self.counters.get(k, 0) + v
        tel.counter("serve/batches")
        tel.counter("serve/images", len(reqs))
        if flight.overlapped:
            tel.counter("serve/overlapped_turns")
        if flight.deferred:
            tel.counter("serve/deferred_masks")
        tel.counter("serve/post_kept", xfer["post_kept"])
        if "post_candidates" in xfer:
            tel.counter("serve/post_candidates", xfer["post_candidates"])
            tel.counter("serve/post_nms_native", xfer["post_nms_native"])
        if "mask_rois" in xfer:
            tel.counter("serve/mask_dispatches", xfer["mask_dispatches"])
            tel.counter("serve/mask_rois", xfer["mask_rois"])
            tel.counter("serve/mask_native", xfer["mask_native"])
        if stream_frames:
            tel.counter("stream/batches")
            tel.counter("stream/batch_frames", stream_frames)
            if len(stream_ids) > 1:
                tel.counter("stream/coalesced_batches")
        if tracer.enabled:
            self._emit_trace_spans(tracer, reqs, now, done, B - len(reqs),
                                   B, flight.phases)
        if self.capture.enabled:
            entries = []
            for r in reqs:
                px, hw = ((r.image, r.raw_hw) if self.opts.serve_e2e
                          else (r.staged, r.staged_hw))
                if px is not None:
                    entries.append((px, hw, r.orig_hw, r.future._result,
                                    r.trace.trace_id
                                    if r.trace is not None else None))
            self.capture.record_batch(entries, self.generation)

    def _emit_trace_spans(self, tracer, reqs: List[_Request],
                          t_start: float, t_done: float, pad: int, B: int,
                          phases: Optional[dict]):
        """The batch-causality spans.  For every traced request in the
        flush: an ``engine/request`` span (rid, batch-peer rids, queue
        position, pad fraction, bucket, occupancy) parented on the
        request's incoming context, an ``engine/dispatch`` child naming
        every rid that shared the device program run, and per-phase
        children (h2d/forward/readback/postprocess) from the measured
        batch phase durations — so a slow trace resolves to WHICH wait:
        queue residence behind peers, a cold compile in the forward, or
        a fat readback."""
        traced = [r for r in reqs if r.trace is not None and r.trace.sampled]
        if not traced:
            return
        with self._lock:
            for r in reqs:
                if r.rid is None:
                    r.rid = self._next_rid
                    self._next_rid += 1
        rids = [r.rid for r in reqs]
        bucket = reqs[0].bucket
        bname = f"{bucket[0]}x{bucket[1]}" if bucket is not None else None
        occupancy = f"{len(reqs)}/{B}"
        service_s = t_done - t_start
        for pos, r in enumerate(reqs):
            ctx = r.trace
            if ctx is None or not ctx.sampled:
                continue
            req_sid = tracer.record(
                ctx, "engine/request", t_done - r.t_enqueue,
                attrs={"rid": r.rid,
                       "peers": [i for i in rids if i != r.rid],
                       "queue_pos": pos,
                       "queue_wait_ms": round(
                           (t_start - r.t_enqueue) * 1e3, 3),
                       "pad_frac": round(pad / B, 4),
                       "bucket": bname, "occupancy": occupancy,
                       "stream": r.stream,
                       "generation": self.generation})
            if req_sid is None:
                continue
            disp_sid = tracer.record(
                TraceContext(ctx.trace_id, req_sid), "engine/dispatch",
                service_s, attrs={"batch_rids": rids, "pad": pad,
                                  "bucket": bname, "occupancy": occupancy})
            if disp_sid is None or not phases:
                continue
            pctx = TraceContext(ctx.trace_id, disp_sid)
            for ph in ("h2d", "forward", "readback", "postprocess", "mask"):
                d = phases.get(ph)
                if d is not None:
                    tracer.record(pctx, f"engine/{ph}", d)

    def _stage(self, name: str, annotate: bool = True) -> telemetry.stage:
        """One use of a stage clock that observes into ``hists[name]``."""
        return telemetry.stage(name, self.hists[name], annotate)

    def _note_first_dispatch(self, shape, kind: str, tel) -> bool:
        """First-seen accounting for one batch's program (registry when
        the predictor carries one, local shape set otherwise) + the
        recompile counters/meta the SLO machinery watches."""
        if self.registry is not None:
            # "serve_predict" is the predictor's default forward program;
            # any other kind is the registry's own name for it
            first = self.predictor.note_dispatch(
                shape, kind=None if kind == "serve_predict" else kind)
        else:
            first = (kind, shape) not in self._seen_shapes
            self._seen_shapes.add((kind, shape))
        if first:
            self.counters["recompiles"] += 1
            self.counters[f"recompiles_{self._dtype}"] += 1
            tel.counter("serve/recompile")
            tel.counter(f"serve/recompile/{self._dtype}")
            tel.meta("recompile", program=kind,
                     shape=[s for s in shape if not isinstance(s, str)],
                     dtype=self._dtype)
        return first

    def _launch_legacy(self, flight: _Flight, tel):
        """PR-3 path, launch half: the host-prepped staging batch goes into
        ``predict`` — until it returns, the h2d of the batch and the
        enqueue (``serve/forward``); on a mask network the batch's pyramid
        is captured there — and the d2h of the four outputs is started."""
        images, im_info = flight.staging.images, flight.staging.im_info
        flight.shape = tuple(images.shape)
        flight.first = self._note_first_dispatch(flight.shape,
                                                 "serve_predict", tel)
        with self._stage("serve/forward") as fwd:
            rois, roi_valid, cls_prob, bbox_deltas, _ = \
                self.predictor.predict(images, im_info)
            if self._has_mask:
                # this batch's pyramid, still on the device: the captured
                # pair stays this batch's whatever predict() runs next
                flight.feats, _ = self.predictor.capture_feats()
            flight.outs = (rois, roi_valid, cls_prob, bbox_deltas)
            _copy_to_host_async(flight.outs)
        flight.phases["forward"] = fwd.seconds

    def _finish_legacy(self, flight: _Flight, tel,
                       tail: Optional[_Flight]) -> dict:
        """PR-3 path, finish half: full score/delta readback, on a mask
        network then the ``tail`` of the batch before, host decode +
        per-class NMS, on a mask network the dispatch of the mask program.
        Returns the batch's counter increments (two h2d arrays — images and
        im_info ship separately into the jit call — one dispatch, one fat
        readback, the post-process's candidates and records); its phase
        seconds for the engine's dispatch sub-spans go into
        ``flight.phases``."""
        import jax

        reqs, phases = flight.reqs, flight.phases
        images, im_info = flight.staging.images, flight.staging.im_info
        # what is left of the wait for the device when the turn gets here
        # (all of it for a lone batch, little once the host is the slower
        # of the two) + what is left of the d2h
        with self._stage("serve/readback") as rb:
            rois, roi_valid, cls_prob, bbox_deltas = jax.device_get(
                flight.outs)
        if tail is not None:
            # the batch before's mask program ran right behind this batch's
            # predict, which is back: its answers go out before this
            # batch's post-process, at most one mask program's wait away
            self._finish_tail(tail)
        if flight.first and self.registry is not None:
            # first dispatch of a shape = its compile: the forward +
            # readback wall is the compile(+first run) cost this program
            # would charge a cold user request
            self.predictor.record_compile_seconds(
                flight.shape, phases["forward"] + rb.seconds)
        cfg = self.cfg
        # per image on the timeline, summed into one observation a batch
        decode = telemetry.stage("serve/post/decode")
        nms = telemetry.stage("serve/post/nms")
        records = telemetry.stage("serve/post/records")  # timeline only
        kept = 0
        held = []
        with self._stage("serve/postprocess", annotate=False) as post:
            for r in reqs:
                b = r.row
                with decode:
                    boxes = decode_image_boxes(rois[b], bbox_deltas[b],
                                               np.asarray(r.im_info))
                with nms:
                    dets_pc = per_class_nms(cls_prob[b], boxes, roi_valid[b],
                                            cfg.NUM_CLASSES, cfg.TEST.THRESH,
                                            cfg.TEST.NMS,
                                            cfg.TEST.MAX_PER_IMAGE)
                with records:
                    recs = detections_to_records(dets_pc)
                    kept += len(recs)
                    if self._has_mask:
                        held.append((r, recs))   # answered with its masks
                    else:
                        r.future._set_result(recs)
        for st in (decode, nms):
            st.book(self.hists[st.name])
        phases.update(readback=rb.seconds, postprocess=post.seconds)
        # the boxes over the threshold that went into the per-class NMS
        # (per_class_nms's ``sel``, all classes and images at once; the
        # rows no live request has count nothing)
        n = len(reqs)
        live = np.zeros(len(images), bool)
        for r in reqs:
            live[r.row] = True
        valid = np.asarray(roi_valid, bool) & live[:, None]
        candidates = int(np.count_nonzero(
            (cls_prob[:, :, 1:cfg.NUM_CLASSES] > cfg.TEST.THRESH)
            & valid[:, :, None]))
        nbytes = int(sum(np.asarray(a).nbytes for a in
                         (rois, roi_valid, cls_prob, bbox_deltas)))
        xfer = {"h2d_transfers": 2, "dispatches": 1, "readbacks": 1,
                "readback_bytes": nbytes,
                "h2d_bytes": int(images.nbytes + im_info.nbytes),
                "post_candidates": candidates,
                "post_kept": kept,
                "post_nms_native":
                    n if native.available("mxr_nms_classes") else 0}
        if cfg.network.HAS_FPN:
            xfer.update(_roi_level_counts(rois, valid))
        if self._has_mask:
            # last: the records are answered with their masks, by the tail
            self._mask_stage(flight, held, len(images), tel)
        return xfer

    def _mask_stage(self, flight: _Flight,
                    held: List[Tuple[_Request, List[dict]]], n_rows: int,
                    tel):
        """The second stage of a mask network's batch, after the host's
        decode + per-class NMS has chosen each image's final records
        (``held``: the live requests with their record lists): the records'
        boxes (scaled frame) and classes go back to the device in ONE
        ``(B, R, 4)`` / ``(B, R)`` pair — ``R`` = ``TEST.MAX_PER_IMAGE``,
        padding rows and slots zero — for ONE dispatch of the mask program
        over the pyramid ``predict`` left there (``flight.feats``), whatever
        the number of live records, zero included: a static shape, so
        nothing compiles after warm-up.  The d2h of the ``(B, R, M, M)``
        probabilities is started, and the stage waits in ``flight.mask``
        for the batch's tail (:meth:`_mask_tail`): in the loop a turn later,
        once the next predict — the one program ahead of it — has been read
        back, so the dispatcher never waits for a mask program queued
        behind a predict (:meth:`_dispatch_loop`).

        Stages: ``serve/mask`` (the whole stage: this dispatch + the tail's
        read-back, paste and answers, one observation a batch, booked by
        the tail — the dispatcher's seconds in it, never the wall across
        two turns; a clock without an annotation) with
        ``serve/mask/forward`` (fill + dispatch) here, and in the tail
        ``serve/mask/readback`` (what is left of the wait for the device +
        the d2h) and ``serve/mask/paste`` (per image on the timeline, one
        observation a batch)."""
        cfg = self.cfg
        R = cfg.TEST.MAX_PER_IMAGE if cfg.TEST.MAX_PER_IMAGE > 0 else 100
        use_native = (cfg.TEST.MASK_PASTE != "host"
                      and native.available("mxr_paste_rle"))
        m = _MaskStage(held, flight.feats, n_rows, R,
                       self.predictor.masks_shape((n_rows, R, 4),
                                                  flight.feats), use_native)
        with m.whole:
            self._mask_dispatch(m, tel)
        # what the tail does not read is not held through the next turn:
        # the predict's outputs, and the pyramid unless a further pass
        # needs it (the device frees it once the program has run)
        if not any(len(recs) > R for _, recs in held):
            m.feats = None
        flight.feats = flight.outs = None
        flight.mask = m

    def _mask_dispatch(self, m: _MaskStage, tel):
        """One pass of the mask program: records ``m.start`` … ``+ R`` of
        every image filled into the ``(B, R)`` pair, the dispatch, the d2h
        started."""
        boxes = np.zeros((m.rows, m.R, 4), np.float32)
        labels = np.zeros((m.rows, m.R), np.int32)
        with self._stage("serve/mask/forward") as fwd:
            for r, recs in m.held:
                part = recs[m.start:m.start + m.R]
                if part:
                    boxes[r.row, :len(part)] = np.asarray(
                        [q["bbox"] for q in part], np.float32) \
                        * np.float32(r.im_info[2])
                    labels[r.row, :len(part)] = [q["cls"] for q in part]
            m.first = self._note_first_dispatch(m.shape, "masks_from_feats",
                                                tel)
            m.probs = self.predictor.predict_masks_cached(
                boxes, labels, token=None, feats=m.feats)
            _copy_to_host_async((m.probs,))
        m.forward_s = fwd.seconds
        m.h2d_bytes = int(boxes.nbytes + labels.nbytes)

    def _mask_tail(self, m: _MaskStage, tel) -> Tuple[dict, float]:
        """The tail of a mask network's batch: one read-back of the mask
        program's probabilities, and per record the paste into the
        request's own raw frame + RLE (``eval.tester.mask_to_rle``, the
        evaluator's function: ``native.paste_rle``, or cv2 + numpy under
        ``TEST.MASK_PASTE = "host"`` or without the library).  Each record
        gains ``"segmentation": {"size": [h, w], "counts": [...]}``; then
        the futures are set.  An image the score-tie rule left with more
        than ``R`` records is drained in further passes of the same
        program, here, each dispatched and read back at once.
        -> (the batch's counter increments, the stage's seconds)."""
        import jax

        from mx_rcnn_tpu.eval.tester import mask_to_rle

        paste = telemetry.stage("serve/mask/paste")
        passes = rois = 0
        with m.whole:
            while True:
                with self._stage("serve/mask/readback") as rb:
                    probs = np.asarray(jax.device_get(m.probs), np.float32)
                m.probs = None
                if m.first and self.registry is not None:
                    self.predictor.record_compile_seconds(
                        m.shape, m.forward_s + rb.seconds,
                        kind="masks_from_feats")
                for r, recs in m.held:
                    h, w = r.orig_hw
                    with paste:
                        for i, rec in enumerate(recs[m.start:m.start + m.R]):
                            rec["segmentation"] = mask_to_rle(
                                probs[r.row, i], rec["bbox"], h, w,
                                native=m.use_native)
                            rois += 1
                passes += 1
                m.start += m.R
                if not any(len(recs) > m.start for _, recs in m.held):
                    break
                self._mask_dispatch(m, tel)
            paste.book(self.hists["serve/mask/paste"])
            for r, recs in m.held:
                r.future._set_result(recs)
        m.whole.book(self.hists["serve/mask"])
        back = passes * int(probs.nbytes)
        return ({"mask_dispatches": passes, "mask_rois": rois,
                 "mask_readback_bytes": back,
                 "mask_native": rois if m.use_native else 0,
                 # the batch's boundary crossings, beside predict's
                 "dispatches": passes, "readbacks": passes,
                 "readback_bytes": back, "h2d_transfers": 2 * passes,
                 "h2d_bytes": passes * m.h2d_bytes},
                m.whole.seconds)

    def _launch_e2e(self, flight: _Flight, tel):
        """Single-dispatch path (``--serve-e2e``), launch half: ONE
        ``device_put`` of the staged uint8 batch + its sidecars
        (``serve/h2d``), ONE fused prep → forward → decode+NMS dispatch
        (registry kind ``serve_e2e``; ``serve/forward``), and the d2h of
        the ``(B, cap, 6)`` detections started."""
        import jax

        reqs, staged = flight.reqs, flight.staging.images
        # the small sidecars by row, like the images; rows nobody wrote
        # get the last request's, so that the device prep sees real sizes
        raw_hw = np.tile(np.asarray(reqs[-1].raw_hw, np.int32),
                         (len(staged), 1))
        ratio = np.full(len(staged), reqs[-1].ratio, np.float32)
        for r in reqs:
            raw_hw[r.row], ratio[r.row] = r.raw_hw, r.ratio
        flip = np.zeros(len(staged), bool)  # serve traffic never flips
        mpi = int(self.cfg.TEST.MAX_PER_IMAGE)
        th = float(self.cfg.TEST.THRESH)
        flight.shape = tuple(staged.shape) + (f"mpi={mpi}", f"th={th:g}")
        flight.first = self._note_first_dispatch(flight.shape, "serve_e2e",
                                                 tel)
        host_args = (staged, raw_hw, ratio,
                     np.asarray(flight.staging.im_info, np.float32), flip)
        flight.h2d_bytes = int(sum(a.nbytes for a in host_args))
        with self._stage("serve/h2d") as h2d:
            # the one host→device transfer: a single put of the argument
            # tuple whose only large buffer is the staged uint8 batch
            args = jax.device_put(host_args)
        with self._stage("serve/forward") as fwd:
            flight.outs = self.predictor.predict_serve_e2e(*args, mpi, th)
            _copy_to_host_async(flight.outs)
        flight.phases.update(h2d=h2d.seconds, forward=fwd.seconds)

    def _finish_e2e(self, flight: _Flight, tel) -> dict:
        """Single-dispatch path, finish half: the cascade's gate, ONE
        readback of the ``(B, cap, 6)`` detections, the records.
        Responses come from ``device_dets_to_per_class`` — the same
        top-k-capped contract as ``--device-postprocess`` eval, so exact
        score ties at the cap may resolve differently from the host-NMS
        path (documented in ``ops.postprocess.device_postprocess``)."""
        import jax

        reqs, phases = flight.reqs, flight.phases
        dets, dvalid = flight.outs
        if self.cascade is not None:
            # on-device confidence gate: fold the (B, cap, 6) detections
            # into per-image hardness while they are STILL device arrays —
            # the gate consumes tensors already on device and reads back
            # (B,) floats, adding zero h2d transfers to the batch
            self.cascade.gate_batch(dets, dvalid, reqs)
        with self._stage("serve/readback") as rb:
            dets, dvalid = jax.device_get((dets, dvalid))
        if flight.first and self.registry is not None:
            self.predictor.record_compile_seconds(
                flight.shape, phases["h2d"] + phases["forward"] + rb.seconds,
                kind="serve_e2e")
        kept = 0
        with self._stage("serve/postprocess") as post:
            for r in reqs:
                dets_pc = device_dets_to_per_class(dets[r.row], dvalid[r.row],
                                                   self.cfg.NUM_CLASSES)
                recs = detections_to_records(dets_pc)
                kept += len(recs)
                r.future._set_result(recs)
        phases.update(readback=rb.seconds, postprocess=post.seconds)
        nbytes = int(np.asarray(dets).nbytes + np.asarray(dvalid).nbytes)
        return {"h2d_transfers": 1, "dispatches": 1, "readbacks": 1,
                "readback_bytes": nbytes,
                "h2d_bytes": flight.h2d_bytes,
                "post_kept": kept}

    # -- introspection ---------------------------------------------------

    def metrics(self) -> dict:
        """The ``/metrics`` payload: counters + live queue state, latency
        quantiles, effective per-bucket policy, and (when a controller is
        attached) its live state.  ``self._lock`` is NOT reentrant (the
        dispatch condition wraps it), so everything that takes its own
        lock — Hist quantiles, ``policy()``, the controller — runs after
        the engine lock is released."""
        with self._lock:
            out = {
                "counters": dict(self.counters),
                "queue_depth": sum(len(q) for q in self._queues.values()),
                "buckets": {f"{h}x{w}": len(q)
                            for (h, w), q in self._queues.items()},
                "options": {"batch_size": self.opts.batch_size,
                            "max_delay_ms": self.opts.max_delay_ms,
                            "max_queue": self.opts.max_queue,
                            "deadline_ms": self.opts.deadline_ms},
                "admit_limit": self._admit_limit,
                "generation": self.generation,
                "ready": (self._ready.is_set() and not self._draining
                          and not self._stop
                          and (self._thread is not None or self._external)),
                "draining": self._draining,
            }
        latency = {}
        for name, h in self.hists.items():
            short = name.split("/", 1)[1]
            for q, tag in ((0.5, "p50_ms"), (0.99, "p99_ms")):
                v = h.quantile(q)
                if v is not None:
                    latency[f"{short}_{tag}"] = round(v * 1e3, 3)
        out["latency"] = latency
        # monotone clocks: (after - before) of two snapshots is a window's
        out["stages"] = {name: h.clock() for name, h in self.hists.items()}
        out["setup"] = dict(self.setup)
        out["t_s"] = time.monotonic()
        # the whole process's CPU seconds (every thread: the dispatcher,
        # the request threads, the runtime's) at the same instant: over two
        # scrapes' t_s, the cores it kept busy, of the cores it may run on
        usage = resource.getrusage(resource.RUSAGE_SELF)
        out["host"] = {"cpu_s": usage.ru_utime + usage.ru_stime,
                       "cores": len(os.sched_getaffinity(0))}
        out["policy"] = self.policy()
        out["dtype"] = self._dtype
        if self.capture.enabled:
            out["flywheel"] = self.capture.metrics()
        tracer = tracectx.get()
        if tracer.enabled:
            out["trace"] = tracer.metrics()
        if self.stream is not None:
            out["stream"] = self.stream.metrics()
        if self.registry is not None:
            out["compile"] = self.registry.snapshot()
        ctrl = self.controller
        if ctrl is not None:
            out["controller"] = ctrl.state()
        return out
