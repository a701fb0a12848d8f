"""Staging batches (``serve/engine.py``): a batch is assembled by the
threads that prepared its images, each into its own row of a reused
per-bucket array, and ``serve/assemble`` is a hand-over.  CPU, tiny
configuration, the shape-faithful stubs of ``test_serve.py``; every wait in
every test has its own limit (no test can hang the run).
"""

import sys
import threading
import time

import numpy as np
import pytest

from mx_rcnn_tpu.data import prepare_image
from mx_rcnn_tpu.data.image import stage_raw_to_bucket
from mx_rcnn_tpu.serve import (DeadlineExceededError, ServeEngine,
                               ServeOptions)
from tests.test_serve import (FakePredictor, make_engine, raw_image,
                              tiny_cfg)


class KeepingPredictor(FakePredictor):
    """Keeps a copy of every array it was handed, taken at the call."""

    def __init__(self, cfg, delay_s=0.0):
        super().__init__(cfg, delay_s)
        self.seen = []

    def predict(self, images, im_info):
        self.seen.append((np.array(images), np.array(im_info)))
        return super().predict(images, im_info)


def keeping_engine(cfg, **opts):
    defaults = dict(batch_size=4, max_delay_ms=1.0, max_queue=16)
    defaults.update(opts)
    return ServeEngine(KeepingPredictor(cfg), cfg, ServeOptions(**defaults))


def wait_booked(engine, limit_s=30.0):
    """A turn books its counters after it has set its answers."""
    deadline = time.monotonic() + limit_s
    with engine._cond:
        while engine._inflight and time.monotonic() < deadline:
            engine._cond.wait(timeout=0.05)


def alone(cfg, img):
    """The response a one-request engine gives for ``img``."""
    engine = make_engine(cfg, batch_size=4, max_delay_ms=0.0).start()
    try:
        return engine.submit(img).result(timeout=30)
    finally:
        engine.stop()


# -- (a) the array handed to the forward, and the responses ----------------


@pytest.mark.parametrize("case", ["full", "partial", "hole"])
def test_the_forward_gets_what_np_stack_gave_on_every_live_row(case):
    cfg = tiny_cfg()
    engine = keeping_engine(cfg, batch_size=4, max_delay_ms=1.0)
    values = {"full": (30, 90, 150, 210), "partial": (40, 120, 200),
              "hole": (30, 90, 150, 210)}[case]
    imgs = [raw_image(60, 100, v) for v in values]
    # pre-start: rows are handed out in submit order, deterministically
    futs = [engine.submit(im, deadline_ms=1.0 if (case, i) == ("hole", 1)
                          else None) for i, im in enumerate(imgs)]
    if case == "hole":
        time.sleep(0.05)   # request 1 expires in the queue, row 1 stays
    engine.start()
    try:
        live = [i for i in range(len(imgs)) if (case, i) != ("hole", 1)]
        results = {i: futs[i].result(timeout=30) for i in live}
        if case == "hole":
            with pytest.raises(DeadlineExceededError):
                futs[1].result(timeout=30)
        wait_booked(engine)
        counters = dict(engine.counters)
        rows = engine.hists["serve/stage_row"].count   # rows written
    finally:
        engine.stop()
    assert len(engine.predictor.seen) == 1
    images, im_info = engine.predictor.seen[0]
    prepared = [prepare_image(im, cfg, cfg.tpu.SCALES[0]) for im in imgs]
    assert images.shape == (4,) + prepared[0][0].shape
    assert images.dtype == prepared[0][0].dtype == np.float32
    # every live request sits in the row it took at submit: what np.stack
    # of the queue gave, except that a hole stays where it was
    want = np.stack([prepared[i][0] for i in live])
    want_info = np.stack([prepared[i][1] for i in live])
    assert np.array_equal(images[live], want)
    assert np.array_equal(im_info[live], want_info)
    for i in live:
        assert results[i] == alone(cfg, imgs[i])
    assert counters["served"] == len(live)
    assert counters["batches"] == 1
    assert rows == counters["requests"] == len(imgs)
    assert counters["assemble_waits"] == 0


# -- (b) one staging batch never serves two buckets ------------------------


def test_two_buckets_fill_two_staging_batches_and_never_share_one():
    cfg = tiny_cfg()
    engine = keeping_engine(cfg, batch_size=2, max_delay_ms=1.0)
    shapes = [(60, 100), (100, 60), (50, 90), (90, 50)]
    imgs = [raw_image(h, w, 40 + 50 * i) for i, (h, w) in enumerate(shapes)]
    futs = [engine.submit(im) for im in imgs]
    land, port = engine.bucket_key(60, 100), engine.bucket_key(100, 60)
    with engine._lock:
        by_bucket = {key: [r.staging for r in q]
                     for key, q in engine._queues.items()}
        lines = {key: list(line) for key, line in engine._staging.items()}
    assert set(by_bucket) == {land, port}
    # each bucket's two requests share its one open batch, rows 0 and 1
    assert by_bucket[land][0] is by_bucket[land][1] is lines[land][0]
    assert by_bucket[port][0] is by_bucket[port][1] is lines[port][0]
    assert lines[land][0] is not lines[port][0]
    assert not np.shares_memory(lines[land][0].images, lines[port][0].images)
    assert lines[land][0].images.shape[1:3] == lines[port][0].images.shape[
        1:3][::-1]
    engine.start()
    try:
        results = [f.result(timeout=30) for f in futs]
        wait_booked(engine)
    finally:
        engine.stop()
    assert sorted(b[0].shape for b in engine.predictor.seen) == sorted(
        [lines[land][0].images.shape, lines[port][0].images.shape])
    for im, dets in zip(imgs, results):
        assert dets == alone(cfg, im)


# -- (c) not handed out again before the read-back has returned ------------


class LateReader(FakePredictor):
    """``predict`` returns at once, like jax's asynchronous dispatch, and
    the "device" reads the host buffer only when the outputs are fetched:
    ``jax.device_get`` calls ``__array__`` on each, which waits for
    ``release`` and scores whatever the buffer holds THEN."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.release = threading.Event()
        self.release.set()
        self.entered = threading.Event()
        self.buffers = []

    def predict(self, images, im_info):
        self.buffers.append(images)
        compute = FakePredictor.predict
        owner, outs = self, {}

        class Out:
            def __init__(self, i):
                self.i = i

            def __array__(self, *a, **k):
                owner.entered.set()
                owner.release.wait(30)
                if not outs:
                    outs["v"] = compute(owner, images, im_info)
                return np.asarray(outs["v"][self.i])

        return Out(0), Out(1), Out(2), Out(3), None


def test_a_staging_batch_is_not_reused_before_its_read_back_returned():
    cfg = tiny_cfg()
    B = 4
    pred = LateReader(cfg)
    engine = ServeEngine(pred, cfg, ServeOptions(
        batch_size=B, max_delay_ms=1.0, max_queue=16))
    try:
        pred.release.clear()
        first = [raw_image(60, 100, 20 + 10 * i) for i in range(B)]
        futs = [engine.submit(im) for im in first]   # pre-start: one batch
        engine.start()
        assert pred.entered.wait(30)       # the turn is inside its read-back
        inflight = pred.buffers[0]
        held = np.array(inflight)
        # 2 x B further requests arrive while the forward is held
        later = [raw_image(60, 100, 100 + 10 * i) for i in range(2 * B)]
        later_futs = [engine.submit(im) for im in later]
        with engine._lock:
            queued = [r.staging for q in engine._queues.values() for r in q]
            free = [s for f in engine._staging_free.values() for s in f]
        assert len(queued) == 2 * B
        for s in queued + free:
            assert not np.shares_memory(s.images, inflight)
        assert np.array_equal(inflight, held)   # nobody wrote into it
        assert not any(f.done() for f in futs)
        pred.release.set()
        results = [f.result(timeout=30) for f in futs]
        later_results = [f.result(timeout=30) for f in later_futs]
        wait_booked(engine)
        with engine._lock:   # and now it is back, to be used again
            free = [s for f in engine._staging_free.values() for s in f]
        assert any(np.shares_memory(s.images, inflight) for s in free)
        assert engine.counters["staging_allocs"] == 4
    finally:
        pred.release.set()
        engine.stop()
    for im, dets in zip(first + later, results + later_results):
        assert dets == alone(cfg, im)


# -- (d) many threads ------------------------------------------------------


def test_32_threads_each_response_is_its_own_images():
    cfg = tiny_cfg()
    B, max_queue, per_thread, n_threads = 4, 64, 10, 32
    engine = ServeEngine(FakePredictor(cfg, delay_s=0.002), cfg, ServeOptions(
        batch_size=B, max_delay_ms=50.0, max_queue=max_queue)).start()
    bound = -(-max_queue // B) + 3      # a bucket: full ones, open, two in
    # flight
    want = {v: FakePredictor.row_score(
        prepare_image(raw_image(60, 100, v), cfg, cfg.tpu.SCALES[0])[0])
        for v in range(10, 250)}
    wrong, allocs_at = [], {}
    start = threading.Barrier(n_threads)

    def client(t):
        start.wait()
        for i in range(per_thread):
            v = 10 + (t * 7 + i * 13) % 240
            dets = engine.submit(raw_image(60, 100, v)).result(timeout=60)
            if len(dets) != 1 or abs(dets[0]["score"] - want[v]) > 1e-5:
                wrong.append((t, i, v, dets))
            if t == 0 and i == 2:
                allocs_at["early"] = engine.counters["staging_allocs"]

    threads = [threading.Thread(target=client, args=(t,), daemon=True)
               for t in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)    # many more hand-overs of the GIL
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(120)
        alive = [th.name for th in threads if th.is_alive()]
        wait_booked(engine)
        counters = dict(engine.counters)
        rows = engine.hists["serve/stage_row"].count   # rows written
    finally:
        sys.setswitchinterval(interval)
        engine.stop()
    assert not alive, alive
    assert not wrong, wrong[:3]
    n = n_threads * per_thread
    assert counters["served"] == counters["requests"] == n
    assert rows == n
    assert counters["rejected"] == counters["deadline_exceeded"] == 0
    # the first burst (32 at once) is the deepest the queue ever gets; one
    # more can follow it: two partial batches in flight beside a full queue
    assert 4 <= counters["staging_allocs"] <= bound
    assert 0 <= counters["staging_allocs"] - allocs_at["early"] <= 1
    assert counters["assemble_waits"] <= counters["served"]


# -- (e) a row that is still being written ---------------------------------


def test_the_dispatcher_waits_for_a_row_whose_copy_is_held_back(monkeypatch):
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=2, max_delay_ms=20000.0)
    slow_value = 200
    slow_prepared, _ = prepare_image(raw_image(60, 100, slow_value), cfg,
                                     cfg.tpu.SCALES[0])
    hold, entered = threading.Event(), threading.Event()
    copyto = np.copyto

    def held_copyto(dst, src, *a, **k):
        if src.shape == slow_prepared.shape and np.array_equal(
                src, slow_prepared):
            entered.set()
            assert hold.wait(30)
        return copyto(dst, src, *a, **k)

    monkeypatch.setattr(np, "copyto", held_copyto)
    engine.start()
    out = {}
    slow = threading.Thread(target=lambda: out.setdefault(
        "slow", engine.submit(raw_image(60, 100, slow_value))))
    try:
        slow.start()
        assert entered.wait(30)            # row 0 taken, its copy held
        quick = engine.submit(raw_image(60, 100, 60))   # row 1: batch full
        time.sleep(0.3)
        # the bucket is full and claimed, and the turn waits in
        # serve/assemble: nothing was sent with the unwritten row
        assert engine.predictor.batches == []
        assert not quick.done()
        hold.set()
        slow.join(30)
        assert not slow.is_alive()
        got_quick = quick.result(timeout=30)
        got_slow = out["slow"].result(timeout=30)
        wait_booked(engine)
        counters = dict(engine.counters)
        rows = engine.hists["serve/stage_row"].count   # rows written
        waited_s = engine.hists["serve/assemble"].to_dict()["sum"]
    finally:
        hold.set()
        engine.stop()
    monkeypatch.undo()
    assert len(engine.predictor.batches) == 1
    # the held row, and the quick one if the claim came before its copy
    # (a notify under the lock) had finished
    assert counters["assemble_waits"] in (1, 2)
    assert rows == counters["served"] == 2
    assert waited_s > 0.2
    assert got_slow == alone(cfg, raw_image(60, 100, slow_value))
    assert got_quick == alone(cfg, raw_image(60, 100, 60))


def test_a_copy_that_raises_fails_its_own_request_and_nobody_waits(
        monkeypatch):
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=2, max_delay_ms=1.0)
    bad_prepared, _ = prepare_image(raw_image(60, 100, 222), cfg,
                                    cfg.tpu.SCALES[0])
    copyto = np.copyto

    def failing_copyto(dst, src, *a, **k):
        if src.shape == bad_prepared.shape and np.array_equal(
                src, bad_prepared):
            raise ValueError("no room in this row")
        return copyto(dst, src, *a, **k)

    monkeypatch.setattr(np, "copyto", failing_copyto)
    engine.start()
    try:
        with pytest.raises(ValueError, match="no room"):
            engine.submit(raw_image(60, 100, 222))
        assert engine.queue_depth() == 0
        # the row it took is a padding row of the next batch
        good = engine.submit(raw_image(60, 100, 60))
        dets = good.result(timeout=30)
        wait_booked(engine)
        counters = dict(engine.counters)
        rows = engine.hists["serve/stage_row"].count   # rows written
    finally:
        engine.stop()
    monkeypatch.undo()
    assert dets == alone(cfg, raw_image(60, 100, 60))
    # the failed copy is a use of the clock too: one a request admitted
    assert rows == counters["requests"] == 2
    assert counters["served"] == 1


# -- (f) --serve-e2e: the cascade's re-submission and capture --------------


class FusedStub:
    """``predict_serve_e2e`` of the right shapes: one detection a row,
    scored by the row's mean staged pixel."""

    CAP = 3

    def __init__(self, cfg):
        self.cfg = cfg

    @staticmethod
    def row_score(staged):
        return float(np.asarray(staged, np.float64).mean() / 300 + 0.1)

    def predict_serve_e2e(self, staged, raw_hw, ratio, im_info, flip,
                          max_per_image, thresh):
        staged = np.asarray(staged)
        B = staged.shape[0]
        dets = np.zeros((B, self.CAP, 6), np.float32)
        valid = np.zeros((B, self.CAP), bool)
        for b in range(B):
            dets[b, 0] = [0, 0, 16, 16, self.row_score(staged[b]), 1]
            valid[b, 0] = True
        return dets, valid


class Recorder:
    """The capture sink and the cascade hook, keeping what they are given
    (references, not copies: that is the point)."""

    enabled = True

    def __init__(self):
        self.entries, self.reqs = [], []

    def record_batch(self, entries, generation):
        self.entries.extend(entries)

    def gate_batch(self, dets, dvalid, reqs):
        self.reqs.extend(reqs)

    def close(self):
        pass


def test_e2e_resubmitted_and_captured_pixels_stay_the_requests_own():
    cfg = tiny_cfg()
    opts = ServeOptions(batch_size=2, max_delay_ms=1.0, max_queue=16,
                        serve_e2e=True)
    small = ServeEngine(FusedStub(cfg), cfg, opts)
    big = ServeEngine(FusedStub(cfg), cfg, opts)
    rec = Recorder()
    small.capture = small.cascade = rec
    small.start()
    big.start()
    rng = np.random.RandomState(5)
    imgs = [rng.randint(0, 255, (60, 100, 3), dtype=np.uint8)
            for _ in range(8)]
    stride = max(cfg.network.IMAGE_STRIDE, cfg.network.RPN_FEAT_STRIDE)
    staged = [stage_raw_to_bucket(im, cfg.tpu.SCALES[0], stride)[0]
              for im in imgs]
    try:
        # four batches of two, one after another: the bucket's staging
        # batches are written again and again
        for i in range(0, 8, 2):
            futs = [small.submit(im) for im in imgs[i:i + 2]]
            for f in futs:
                f.result(timeout=30)
            wait_booked(small)
        assert small.counters["staging_allocs"] == 4
        assert small.counters["batches"] >= 4
        assert len(rec.reqs) == len(rec.entries) == 8
        with small._lock:
            buffers = [s.images for f in small._staging_free.values()
                       for s in f]
        assert buffers and buffers[0].dtype == np.uint8
        for req, entry, want in zip(rec.reqs, rec.entries, staged):
            for px in (req.image, entry[0]):
                assert np.array_equal(px, want)
                assert not any(np.shares_memory(px, b) for b in buffers)
        # the cascade re-submits the first requests' pixels, byte for byte
        again = [big.submit_staged(r.image, r.raw_hw, r.ratio, r.im_info,
                                   r.orig_hw).result(timeout=30)
                 for r in rec.reqs[:3]]
    finally:
        small.stop()
        big.stop()
    for dets, want in zip(again, staged[:3]):
        assert len(dets) == 1
        assert abs(dets[0]["score"] - FusedStub.row_score(want)) < 1e-6
