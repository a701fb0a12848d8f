"""Batch loaders (reference ``rcnn/core/loader.py``: ``AnchorLoader``,
``ROIIter``, ``TestLoader``).

Differences by design (all SURVEY §7 step-4 decisions):

* No ``feat_sym.infer_shape`` / label pre-computation — anchor and RoI
  targets are assigned *inside the jitted graph*; the loader ships
  (images, im_info, gt_boxes·scale, gt_classes, gt_valid) only.
* Static shapes: images land in per-orientation scale buckets, gt is
  padded to MAX_GT.  Aspect-ratio grouping (the reference's
  ``aspect_grouping``) both balances batches and selects the compiled
  program: one batch never mixes bucket shapes.
* Host→device overlap: a background thread prepares the next batch(es)
  while the device runs the current step (replaces MXNet's threaded
  ``PrefetchingIter``).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.data.image import (get_image, resize_to_bucket,
                                    space_to_depth2, stage_raw_to_bucket,
                                    transform_image)
from mx_rcnn_tpu.logger import logger

# Fault isolation (train loaders): one missing/corrupt image substitutes a
# deterministic neighbor record instead of killing the producer thread, but
# this many failures IN A ROW means the breakage is systemic (unmounted
# filesystem, wrong dataset path) and must raise, not silently retrain on
# substitutes.  Class-level so tests/operators can widen it.
MAX_CONSECUTIVE_BAD_RECORDS = 8


def prepare_image(im: np.ndarray, cfg: Config,
                  scale: Tuple[int, int]) -> Tuple[np.ndarray, np.ndarray]:
    """Raw RGB HWC image → (bucket-padded network input, im_info) — the
    image half of ``_load_record``, shared with the serve engine
    (``mx_rcnn_tpu/serve``) so an online request goes through byte-for-byte
    the same transform chain as an eval batch: pixel normalize → resize by
    the reference rule → zero-pad into the orientation's static bucket →
    optional host space-to-depth, or rows flattened to (H, W·3)."""
    im = transform_image(im, cfg.network.PIXEL_MEANS, cfg.network.PIXEL_STDS)
    stride = max(cfg.network.IMAGE_STRIDE, cfg.network.RPN_FEAT_STRIDE)
    padded, s, (eh, ew) = resize_to_bucket(im, scale, stride)
    if cfg.network.HOST_S2D:
        padded = space_to_depth2(padded)
    elif cfg.network.HOST_ROWS:
        padded = padded.reshape(padded.shape[0], -1)
    return padded, np.asarray([eh, ew, s], np.float32)


def _load_record(rec: dict, cfg: Config, scale: Tuple[int, int],
                 with_masks: bool = False) -> dict:
    """roidb record → one transformed sample (host numpy).

    ``with_masks``: rasterize gt masks (train loaders under HAS_MASK only —
    eval and proposal loaders never consume them)."""
    device_prep = getattr(cfg.tpu, "DEVICE_PREP", False)
    flipped = bool(rec.get("flipped", False))
    if "replay_npz" in rec:  # flywheel replay shard (data/replay.py)
        from mx_rcnn_tpu.data.replay import load_replay_pixels

        # raises on a corrupt/truncated shard — train loaders land in the
        # bad-record substitution path below, eval loaders stay strict
        im = load_replay_pixels(rec)
        if flipped and not device_prep:
            im = im[:, ::-1, :]
    elif "image_array" in rec:  # synthetic dataset ships pixels inline
        im = rec["image_array"]
        if flipped and not device_prep:  # device prep mirrors on device
            im = im[:, ::-1, :]
    else:
        im = get_image(rec["image"], flipped=flipped and not device_prep)
    if device_prep:
        # ship raw uint8 staged into the output bucket; the jitted
        # device_prep program does resize/flip/normalize/pad (+ s2d).
        # The pixel key stays "images" so every shape/dtype-agnostic
        # consumer (worker shm handover, group assembly, _stack) flows
        # unchanged; the sidecar keys are consumed by DevicePrep hooks.
        stride = max(cfg.network.IMAGE_STRIDE, cfg.network.RPN_FEAT_STRIDE)
        padded, raw_hw, ratio, im_info = stage_raw_to_bucket(
            np.ascontiguousarray(im), scale, stride)
    else:
        padded, im_info = prepare_image(im, cfg, scale)
    s = float(im_info[2])

    g = cfg.tpu.MAX_GT
    boxes = np.zeros((g, 4), np.float32)
    classes = np.zeros((g,), np.int32)
    valid = np.zeros((g,), bool)
    n = min(len(rec["boxes"]), g)
    if n:
        boxes[:n] = rec["boxes"][:n] * s  # gt scaled into the resized frame
        classes[:n] = rec["gt_classes"][:n]
        valid[:n] = True
    out = dict(images=padded, im_info=im_info,
               gt_boxes=boxes, gt_classes=classes, gt_valid=valid)
    if device_prep:
        out["raw_hw"] = raw_hw
        out["prep_ratio"] = ratio
        out["flip"] = np.bool_(flipped)
    if with_masks and cfg.network.HAS_MASK:
        from mx_rcnn_tpu.data.mask import rasterize_gt_masks

        out["gt_masks"] = rasterize_gt_masks(
            rec.get("segmentation"), rec["boxes"], rec["width"],
            rec.get("flipped", False), g)
    return out


def _load_record_isolated(roidb: list, i: int, cfg: Config,
                          scale: Tuple[int, int], with_masks: bool = False,
                          state: Optional[list] = None) -> Tuple[int, dict]:
    """``_load_record`` with fault isolation for TRAIN loaders: a failing
    record (missing/corrupt image) substitutes the next roidb record
    deterministically instead of killing the producer thread, bumping the
    ``loader/bad_record`` telemetry counter per failure.

    ``state`` is a single-element mutable list holding the CONSECUTIVE
    failure count across calls from one producer generator — it resets on
    every success, and crossing ``MAX_CONSECUTIVE_BAD_RECORDS`` raises
    (systemic breakage must not silently train on substitutes).

    Returns ``(actual_index, sample)`` so callers that pair the sample
    with other per-record data (ROIIter's proposals) stay consistent
    with the substituted record.  Eval loaders stay strict: a bad record
    in evaluation silently changes the metric and must raise.
    """
    n = len(roidb)
    state = state if state is not None else [0]
    attempt = 0
    while True:
        j = (i + attempt) % n
        try:
            out = _load_record(roidb[j], cfg, scale, with_masks=with_masks)
            state[0] = 0
            return j, out
        except Exception as e:  # noqa: BLE001 — isolate, count, bound
            state[0] += 1
            telemetry.get().counter("loader/bad_record")
            if state[0] >= MAX_CONSECUTIVE_BAD_RECORDS:
                telemetry.get().dump_flight(
                    "loader_systemic", consecutive_bad=state[0],
                    last_index=j, error=f"{type(e).__name__}: {e}"[:500])
                raise RuntimeError(
                    f"{state[0]} consecutive roidb records failed to load "
                    f"(last: index {j}, {type(e).__name__}: {e}) — this "
                    f"looks systemic (wrong dataset path? unmounted "
                    f"filesystem?), not a stray corrupt image") from e
            logger.warning("bad roidb record %d (%s: %s) — substituting "
                           "record %d [loader/bad_record]",
                           j, type(e).__name__, e, (j + 1) % n)
            attempt += 1


def _stack(samples: List[dict]) -> Dict[str, np.ndarray]:
    return {k: np.stack([s[k] for s in samples]) for k in samples[0]}


def _iter_samples(roidb: list, cfg: Config, plan, part_fn, pool,
                  with_masks: bool = False) -> Iterator[Tuple[int, dict]]:
    """Yield ``(actual_index, sample)`` for every row this process owns,
    in plan order — through the multi-worker ``pool`` when one is set, on
    the calling (producer) thread otherwise.  Both paths run the same
    ``_load_record_isolated`` per task, so the output stream is identical
    sample for sample; only the consecutive-bad-record budget is scoped
    differently (per epoch serially, per worker with a pool — either way
    ``MAX_CONSECUTIVE_BAD_RECORDS`` failures in a row on one producer is
    systemic and raises)."""
    tasks = [(int(i), scale) for chunk, scale in plan
             for i in part_fn(chunk)]
    if pool is not None:
        yield from pool.imap_records(tasks, with_masks=with_masks)
        return
    fail_state = [0]
    for i, scale in tasks:
        yield _load_record_isolated(roidb, i, cfg, scale,
                                    with_masks=with_masks, state=fail_state)


class _Prefetcher:
    """Runs a batch-producing generator in a daemon thread with a bounded
    queue (depth = cfg.tpu.PREFETCH).  Closing (or GC of) the iterator stops
    the producer — an abandoned consumer must not leave a thread parked on a
    full queue pinning batches.

    ``put``: optional callable applied to each batch ON THE PRODUCER THREAD
    before it is queued — the device double-buffering hook (round-2 weakness
    3: preparing host numpy but transferring synchronously inside step
    dispatch leaves the transfer on the critical path).  ``fit`` installs
    ``jax.device_put`` (with the mesh sharding when data-parallel) here, so
    the host→device copy is in flight while the previous step computes;
    ``device_put`` only enqueues the transfer, so the producer thread never
    blocks on the device.

    Telemetry (active sink at construction; the no-op sink costs one
    attribute check per batch): producer-side ``loader/produce`` (host
    batch assembly), ``loader/put_transfer`` (the ``put`` hook — the
    device transfer when double-buffering) and ``loader/queue_full_wait``
    (producer blocked on a full queue = consumer is the bottleneck);
    consumer-side ``loader/queue_depth`` gauge sampled at every get (a
    persistently empty queue = producer is the bottleneck).

    ``watchdog_s``: consumer-side timeout on the blocking get — a producer
    stuck past it (hung filesystem read, deadlocked ``put`` hook) raises a
    diagnostic naming the producer state instead of hanging the training
    loop forever.  The timeout is measured from the producer's last
    HEARTBEAT (one per queued batch), so a slow-but-advancing producer is
    never killed.  <= 0 disables."""

    def __init__(self, gen, depth: int, put=None, watchdog_s: float = 600.0):
        self._q: queue.Queue = queue.Queue(maxsize=max(depth, 1))
        self._err = None
        self._stop = threading.Event()
        self._tel = telemetry.get()
        self._watchdog_s = watchdog_s
        self._beat = time.monotonic()

        def enqueue(item) -> bool:
            """Blocking put that honors close(); False once stopped."""
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.2)
                    self._beat = time.monotonic()
                    return True
                except queue.Full:
                    self._beat = time.monotonic()  # blocked-on-full is alive
                    continue
            return False

        def run():
            # Re-stamp the heartbeat the moment the producer THREAD starts:
            # the watchdog clock otherwise runs from __init__, and a slow
            # epoch boundary (worker-pool spawn, scheduler delay between
            # construction and thread start) would count against the budget
            # and trip a spurious prefetch_watchdog flight dump on a fresh
            # prefetcher.
            self._beat = time.monotonic()
            tel = self._tel
            try:
                if not tel.enabled:  # untimed hot path: one check per epoch
                    for item in gen:
                        if put is not None:
                            item = put(item)
                        if not enqueue(item):
                            return
                else:
                    t_prod = time.perf_counter()
                    for item in gen:
                        dt_prod = time.perf_counter() - t_prod
                        tel.add("loader/produce", dt_prod)
                        tel.observe("loader/produce", dt_prod)
                        if put is not None:
                            with tel.span("loader/put_transfer"):
                                item = put(item)
                        t_full = time.perf_counter()
                        if not enqueue(item):
                            return
                        tel.add("loader/queue_full_wait",
                                time.perf_counter() - t_full)
                        t_prod = time.perf_counter()
            except BaseException as e:  # surfaced on the consumer side
                self._err = e
            finally:
                while True:  # sentinel must land even on a full queue
                    try:
                        self._q.put(None, timeout=0.2)
                        break
                    except queue.Full:
                        if self._stop.is_set():
                            break
                        continue

        self._t = threading.Thread(target=run, daemon=True)
        self._t.start()

    def close(self, timeout: float = 5.0):
        """Stop the producer AND join its thread (bounded) — repeated
        ``fit()`` calls over one loader must not accumulate daemon threads
        parked in ``enqueue``.  Draining the queue first unblocks a
        producer waiting on a full queue so the join is fast."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._t.join(timeout=timeout)
        if self._t.is_alive():
            logger.warning("prefetch producer thread did not exit within "
                           "%.1fs of close() — still parked in the source "
                           "generator?", timeout)

    def __del__(self):
        self._stop.set()  # no join in GC: finalizers must not block

    def _get(self):
        """Blocking get with the producer watchdog (see class docstring)."""
        if self._watchdog_s <= 0:
            return self._q.get()
        poll = min(self._watchdog_s, 5.0)
        while True:
            try:
                return self._q.get(timeout=poll)
            except queue.Empty:
                age = time.monotonic() - self._beat
                if age < self._watchdog_s and self._t.is_alive():
                    continue  # slow but advancing (or just started)
                telemetry.get().dump_flight(
                    "prefetch_watchdog", age_s=round(age, 1),
                    producer_alive=self._t.is_alive())
                raise RuntimeError(
                    f"prefetch queue empty with no producer heartbeat for "
                    f"{age:.0f}s (watchdog {self._watchdog_s:.0f}s) — "
                    f"producer thread "
                    f"{'alive' if self._t.is_alive() else 'DEAD'}, "
                    f"stop_requested={self._stop.is_set()}, "
                    f"qsize={self._q.qsize()}: the producer is stuck (hung "
                    f"filesystem read? deadlocked put hook?) or died "
                    f"without delivering its end-of-epoch sentinel") \
                    from None

    def __iter__(self):
        tel = self._tel
        try:
            while True:
                if tel.enabled:
                    # sampled BEFORE the blocking get: a persistently-zero
                    # depth means the consumer outruns the producer
                    tel.gauge("loader/queue_depth", self._q.qsize())
                item = self._get()
                if item is None:
                    if self._err is not None:
                        raise self._err
                    return
                yield item
        finally:
            self.close()


class AnchorLoader:
    """End-to-end / RPN training loader (reference ``AnchorLoader``).

    Iterable over epochs; each pass yields dict batches.  ``batch_size`` is
    the GLOBAL images-per-step (the trainer shards over the mesh data axis).
    Incomplete trailing groups are wrapped by re-sampling from the group
    (reference pads the last batch by wrapping indices).

    ``num_parts``/``part_index`` (the MXNet ``mx.io.DataIter`` partition
    kwargs used with ``KVStore('dist_sync')``) make the loader multi-host:
    the FULL epoch schedule — shuffle, aspect buckets, scale choice,
    wrap-padding — is computed from the (replicated) roidb with the shared
    seed, identical on every process, and each process then loads and
    yields only rows ``[part_index·B/num_parts, (part_index+1)·B/num_parts)``
    of every global batch.  Identical schedules are what keep all
    processes dispatching the same compiled program in lockstep;
    ``parallel.assert_loader_partition`` checks the slice matches the mesh
    row shards this process owns.  ``batch_size`` and ``steps_per_epoch``
    keep their GLOBAL meaning.
    """

    def __init__(self, roidb: list, cfg: Config, batch_size: int,
                 shuffle: bool = True, seed: int = 0,
                 num_parts: int = 1, part_index: int = 0,
                 replay_roidb: Optional[list] = None,
                 replay_ratio: float = 0.0):
        if not roidb:
            raise ValueError("empty roidb")
        if not (0 <= part_index < num_parts):
            raise ValueError(f"part_index {part_index} not in [0, {num_parts})")
        if batch_size % num_parts:
            raise ValueError(f"batch_size {batch_size} does not divide over "
                             f"{num_parts} parts")
        if not (0.0 <= replay_ratio < 1.0):
            raise ValueError(f"replay_ratio must be in [0, 1), "
                             f"got {replay_ratio}")
        # flywheel replay mixing (data/replay.py): mined records append
        # AFTER the base roidb; the epoch schedule (groups, steps, wrap)
        # is computed from the base alone, and each assembled batch then
        # substitutes ~replay_ratio of its slots with same-orientation
        # replay records.  All draws come from self._rng at plan time, so
        # the mix is bit-reproducible under advance_epochs/skip_next.
        replay_roidb = list(replay_roidb) if replay_roidb else []
        base_n = len(roidb)
        self.roidb = list(roidb) + replay_roidb
        self.replay_ratio = replay_ratio if replay_roidb else 0.0
        self._replay_groups = [
            [base_n + i for i, r in enumerate(replay_roidb)
             if r["width"] >= r["height"]],
            [base_n + i for i, r in enumerate(replay_roidb)
             if r["width"] < r["height"]],
        ]
        self.replay_substituted = 0  # cumulative slots replaced
        self.cfg = cfg
        self.batch_size = batch_size
        self.num_parts = num_parts
        self.part_index = part_index
        self.shuffle = shuffle
        # device double-buffering hook: when set (``fit`` installs the
        # plan-aware device_put), batches arrive on-device, transfer
        # overlapped with the previous step's compute
        self.put = None
        # generator transform applied around the producer ON ITS THREAD
        # (before ``put``): ``fit`` installs the steps_per_dispatch group
        # assembler here so k-batch stacking + transfer overlap the device
        # just like the k=1 ``put`` path (round-4 weakness 2: consumer-side
        # stacking shipped each group synchronously)
        self.wrap = None
        # multi-worker host pipeline (cfg.tpu.LOADER_WORKERS > 0): created
        # lazily on first iteration, REUSED across epochs (the shm ring is
        # allocated once), torn down by close_workers()/GC
        self._pool = None
        self._rng = np.random.RandomState(seed)
        self._skip = 0  # one-shot batch skip armed by skip_next()
        # aspect grouping: horizontal (w>=h) vs vertical image index pools
        self._groups = [
            [i for i, r in enumerate(roidb) if r["width"] >= r["height"]],
            [i for i, r in enumerate(roidb) if r["width"] < r["height"]],
        ]
        self._len = sum(len(g) // batch_size + (1 if len(g) % batch_size else 0)
                        for g in self._groups if g)

    def __len__(self) -> int:
        return self._len

    @property
    def steps_per_epoch(self) -> int:
        return self._len

    def _epoch_indices(self) -> List[np.ndarray]:
        batches = []
        for gi, g in enumerate(self._groups):
            if not g:
                continue
            idx = np.asarray(g)
            if self.shuffle:
                self._rng.shuffle(idx)
            pool = (self._replay_groups[gi]
                    if self.replay_ratio > 0 else [])
            for i in range(0, len(idx), self.batch_size):
                chunk = idx[i:i + self.batch_size]
                if len(chunk) < self.batch_size:  # wrap like the reference
                    extra = self._rng.choice(idx, self.batch_size - len(chunk))
                    chunk = np.concatenate([chunk, extra])
                if pool:
                    # replay substitution, drawn from the SAME RandomState
                    # as the rest of the plan (never wall clock) — the mix
                    # replays bit-identically on resume
                    mask = self._rng.rand(len(chunk)) < self.replay_ratio
                    k = int(mask.sum())
                    if k:
                        chunk = chunk.copy()
                        chunk[mask] = self._rng.choice(pool, size=k)
                        self.replay_substituted += k
                        telemetry.get().counter("flywheel/replayed", k)
                batches.append(chunk)
        if self.shuffle:
            order = self._rng.permutation(len(batches))
            batches = [batches[i] for i in order]
        return batches

    def _epoch_plan(self) -> List[Tuple[np.ndarray, Tuple[int, int]]]:
        """(batch indices, scale bucket) for one epoch.

        Multi-scale training: one scale bucket per BATCH (upstream
        py-faster-rcnn samples cfg.TRAIN.SCALES per image; with
        BATCH_IMAGES=1 per-batch ≡ per-image, and for larger batches it
        preserves the one-bucket-per-batch static-shape invariant — each
        (scale, orientation) pair is its own compiled program).
        Deterministic loaders (shuffle=False: eval, proposal dumps) pin
        SCALES[0] like the reference's single-scale TEST path.

        All RNG draws happen here, on the caller's thread at epoch start —
        the producer generator must stay RNG-free because an abandoned
        prefetch thread can overlap a re-iteration's new thread, and the
        shared RandomState is not thread-safe.
        """
        batches = self._epoch_indices()
        scales = self.cfg.tpu.SCALES
        if self.shuffle and len(scales) > 1:
            chosen = [scales[self._rng.randint(len(scales))] for _ in batches]
        else:
            chosen = [scales[0]] * len(batches)
        return list(zip(batches, chosen))

    # -- deterministic fast-forward (fit(auto_resume) mid-epoch resume) ---

    def advance_epochs(self, n: int) -> None:
        """Draw-and-discard ``n`` epoch plans, advancing the shared
        RandomState exactly as ``n`` real iterations would — epoch k's
        plan depends on the k prior epochs' draws, so resuming at epoch k
        must burn the first k plans to reproduce the original schedule."""
        for _ in range(n):
            self._epoch_plan()

    def skip_next(self, n: int) -> None:
        """Arm a one-shot skip: the NEXT iteration drops its first ``n``
        batches (consumed before the interruption).  The full plan is
        still generated first — RNG draws are position-dependent, so the
        tail batches come out identical to the uninterrupted epoch."""
        if n < 0:
            raise ValueError(f"skip_next: n must be >= 0, got {n}")
        self._skip = n

    def _take_epoch_plan(self) -> List[Tuple[np.ndarray, Tuple[int, int]]]:
        """One epoch's plan with any armed skip applied (and disarmed)."""
        plan = self._epoch_plan()  # full draw FIRST: keeps RNG in sequence
        skip, self._skip = self._skip, 0
        if skip:
            if skip > len(plan):
                raise ValueError(
                    f"skip_next({skip}) exceeds the epoch's {len(plan)} "
                    f"batches — resume position does not match this "
                    f"loader's schedule (different seed or batch size?)")
            plan = plan[skip:]
        return plan

    def _part(self, chunk: np.ndarray) -> np.ndarray:
        """This process's contiguous row slice of a global batch."""
        bl = self.batch_size // self.num_parts
        return chunk[self.part_index * bl:(self.part_index + 1) * bl]

    def _ensure_pool(self):
        """Create the worker pool on first use (consumer thread — forking
        from the prefetch producer thread would snapshot mid-mutation
        state).  workers=0 (the default) keeps today's serial producer,
        bit for bit."""
        workers = int(getattr(self.cfg.tpu, "LOADER_WORKERS", 0))
        if workers > 0 and self._pool is None:
            from mx_rcnn_tpu.data.workers import WorkerPool

            self._pool = WorkerPool(self.cfg, self.roidb,
                                    num_workers=workers)
        return self._pool

    def close_workers(self):
        """Tear down the worker pool (processes + shm segment).  Idempotent;
        the next iteration recreates it."""
        if self._pool is not None:
            self._pool.close()
            self._pool = None

    def __del__(self):
        try:
            self.close_workers()
        except Exception:
            pass

    def _produce(self, plan) -> Iterator[Dict[str, np.ndarray]]:
        bl = self.batch_size // self.num_parts
        samples: List[dict] = []
        for _, s in _iter_samples(self.roidb, self.cfg, plan, self._part,
                                  self._pool, with_masks=True):
            samples.append(s)
            if len(samples) == bl:
                yield _stack(samples)
                samples = []

    def __iter__(self):
        plan = self._take_epoch_plan()  # RNG on the consumer thread only
        self._ensure_pool()
        gen = self._produce(plan)
        if self.wrap is not None:
            gen = self.wrap(gen)
        return iter(_Prefetcher(gen, self.cfg.tpu.PREFETCH, put=self.put,
                                watchdog_s=self.cfg.tpu.PREFETCH_WATCHDOG_S))


class TestLoader:
    """Eval loader (reference ``TestLoader``): sequential, no shuffle, no gt
    needed; batch padded with repeats of the last image (mask via
    ``batch_valid``)."""

    __test__ = False  # not a pytest class

    def __init__(self, roidb: list, cfg: Config, batch_size: int = 1,
                 prefetch: Optional[int] = None,
                 device_prep: bool = False):
        self.roidb = roidb
        if getattr(cfg.tpu, "DEVICE_PREP", False) and not device_prep:
            # opt-in per loader: a train cfg with DEVICE_PREP on reaches
            # here from drivers whose consumer installs no prep hook
            # (proposal dumps, bench oracles) — those stay on the
            # bit-identical host transform.  ``device_prep=True`` (test.py
            # --device-prep) keeps the sidecars; the Predictor's
            # ``batch_put`` then preps on device (same jitted kernel and
            # host-bilinear parity pin as train; mesh plans raise at
            # Predictor construction).
            import dataclasses as _dc

            cfg = _dc.replace(cfg, tpu=_dc.replace(cfg.tpu,
                                                   DEVICE_PREP=False))
        self.cfg = cfg
        self.batch_size = batch_size
        # prefetch depth override: the overlapped evaluator keeps more
        # batches in flight than the train default assumes, so the decode
        # pipeline must stay ahead of the wider dispatch window
        self.prefetch = (int(prefetch) if prefetch is not None
                         else cfg.tpu.PREFETCH)
        # double-buffering hook (Predictor.batch_put): transfers the
        # device-bound keys from the prefetch thread, keeps indices/
        # batch_valid host-side
        self.put = None

    def __len__(self) -> int:
        n = len(self.roidb)
        return (n + self.batch_size - 1) // self.batch_size

    def __iter__(self):
        def produce():
            scale = self.cfg.tpu.SCALES[0]
            n = len(self.roidb)
            for start in range(0, n, self.batch_size):
                idx = list(range(start, min(start + self.batch_size, n)))
                pad = self.batch_size - len(idx)
                samples = [_load_record(self.roidb[i], self.cfg, scale)
                           for i in idx]
                samples += [samples[-1]] * pad
                batch = _stack(samples)
                batch["indices"] = np.asarray(idx + [idx[-1]] * pad, np.int32)
                batch["batch_valid"] = np.asarray([True] * len(idx) + [False] * pad)
                yield batch

        # strict loads by design (no fault isolation): a silently
        # substituted record would corrupt the eval metric
        return iter(_Prefetcher(
            produce(), self.prefetch, put=self.put,
            watchdog_s=self.cfg.tpu.PREFETCH_WATCHDOG_S))


class ROIIter:
    """Fast-RCNN training loader over cached proposals (reference
    ``ROIIter`` — alternate-training steps 3/6).  Each roidb record carries a
    ``proposals`` (P, 4) array dumped by ``eval.generate_proposals``; they
    are padded/truncated to ``cfg.TRAIN.RPN_POST_NMS_TOP_N`` rows and
    sampled in-graph by ``rcnn_train``."""

    def __init__(self, roidb: list, cfg: Config, batch_size: int,
                 shuffle: bool = True, seed: int = 0,
                 num_parts: int = 1, part_index: int = 0):
        self._inner = AnchorLoader(roidb, cfg, batch_size, shuffle, seed,
                                   num_parts=num_parts, part_index=part_index)
        self.cfg = cfg
        self.batch_size = batch_size
        self.num_parts = num_parts
        self.part_index = part_index
        self.put = None  # same double-buffering hook as AnchorLoader
        self.wrap = None  # same producer-thread group-assembly hook
        cap = cfg.TRAIN.RPN_POST_NMS_TOP_N
        over = sum(len(r.get("proposals", ())) > cap for r in roidb)
        if over:
            from mx_rcnn_tpu.logger import logger

            logger.warning(
                "%d/%d images carry more than TRAIN.RPN_POST_NMS_TOP_N=%d "
                "proposals; ROIIter keeps the FIRST %d rows — fine for "
                "score-sorted RPN caches, lossy for unranked sources like "
                "selective search (raise the cap if the tail matters)",
                over, len(roidb), cap, cap)

    def __len__(self) -> int:
        return len(self._inner)

    @property
    def steps_per_epoch(self) -> int:
        return len(self._inner)

    def advance_epochs(self, n: int) -> None:
        self._inner.advance_epochs(n)

    def skip_next(self, n: int) -> None:
        self._inner.skip_next(n)

    def close_workers(self):
        self._inner.close_workers()

    def __iter__(self):
        cfg = self.cfg
        p_max = cfg.TRAIN.RPN_POST_NMS_TOP_N
        # same per-batch scale-bucket plan as AnchorLoader (upstream samples
        # TRAIN.SCALES in the Fast-RCNN path too); proposals are in the
        # original image frame and rescale by each batch's own im_scale
        plan = self._inner._take_epoch_plan()
        pool = self._inner._ensure_pool()
        roidb = self._inner.roidb
        bl = self.batch_size // self.num_parts

        def produce():
            samples = []
            for j, s in _iter_samples(roidb, cfg, plan, self._inner._part,
                                      pool):
                # the substituted index pairs the sample with ITS OWN
                # proposals — mixing record j's pixels with record i's
                # rois would train on garbage.  Proposal attach stays in
                # the parent (workers ship pixels + gt only; proposal
                # arrays live in the parent's roidb either way)
                rec = roidb[j]
                props = np.asarray(rec.get("proposals",
                                           np.zeros((0, 4))), np.float32)
                rois = np.zeros((p_max, 4), np.float32)
                rvalid = np.zeros((p_max,), bool)
                n = min(len(props), p_max)
                if n:
                    rois[:n] = props[:n] * s["im_info"][2]
                    rvalid[:n] = True
                s["rois"] = rois
                s["roi_valid"] = rvalid
                samples.append(s)
                if len(samples) == bl:
                    yield _stack(samples)
                    samples = []

        gen = produce()
        if self.wrap is not None:
            gen = self.wrap(gen)
        return iter(_Prefetcher(gen, cfg.tpu.PREFETCH, put=self.put,
                                watchdog_s=cfg.tpu.PREFETCH_WATCHDOG_S))
