"""A second batch in flight (``serve/engine.py``, PR 33): the dispatcher
launches the next due batch before it reads back the one in flight, at most
two are launched and unfinished, and the serial order falls out by itself
whenever nothing else is due.  On a mask network (PR 38) a batch's mask
read-back, paste and answers are its tail, finished in the next turn
right after the next predict's read-back (section (i)).  CPU, tiny
configurations, the shape-faithful stubs of ``test_serve.py`` / ``test_serve_staging.py`` and, for the masks,
``test_serve_masks.py``'s tiny real model; every wait in every test has its
own limit (no test can hang the run).
"""

import threading
import time

import numpy as np
import pytest

from mx_rcnn_tpu.serve import RejectedError, ServeEngine, ServeOptions
from tests.test_serve import FakePredictor, raw_image, tiny_cfg
from tests.test_serve_staging import FusedStub, alone, wait_booked


class HeldOutputs(FakePredictor):
    """``predict`` returns at once, like jax's asynchronous dispatch; the
    outputs of batch ``k`` can be fetched (``jax.device_get`` calls
    ``__array__``) only once ``release[k]`` is set, and score whatever the
    host buffer holds THEN — ``test_serve_staging.LateReader`` with one
    event a batch.  ``fail_predict`` / ``fail_fetch``: batch numbers whose
    ``predict`` / read-back raises."""

    def __init__(self, cfg, held=True, fail_predict=(), fail_fetch=()):
        super().__init__(cfg)
        self.held = held
        self.fail_predict, self.fail_fetch = set(fail_predict), set(fail_fetch)
        self.release, self.entered, self.fetched = [], [], []
        self.buffers = []
        self.engine = None          # set by the test: _inflight at a launch
        self.inflight_at_launch = []
        self.fetched_at_launch = []
        # ("P", k) at each predict, ("rP", k) once its read-back is done
        self.log = []
        self.lock = threading.Lock()

    def launched(self):
        with self.lock:
            return len(self.buffers)

    def in_read_back(self, k, limit_s=30.0):
        """The dispatcher has launched batch ``k`` and is inside its
        read-back (which waits for ``release[k]``)."""
        return (wait_for(lambda: self.launched() > k, limit_s)
                and self.entered[k].wait(limit_s))

    def release_all(self):
        with self.lock:
            self.held = False
            for ev in self.release:
                ev.set()

    def predict(self, images, im_info):
        compute = FakePredictor.predict
        owner, outs = self, {}
        with self.lock:
            k = len(self.buffers)
            self.buffers.append(images)
            rel, ent, got = (threading.Event(), threading.Event(),
                             threading.Event())
            if not self.held:
                rel.set()
            self.release.append(rel)
            self.entered.append(ent)
            self.fetched.append(got)
            if self.engine is not None:
                self.inflight_at_launch.append(self.engine._inflight)
            self.fetched_at_launch.append(
                [e.is_set() for e in self.fetched[:k]])
            self.log.append(("P", k))
        if k in self.fail_predict:
            raise RuntimeError(f"predict of batch {k} failed")

        class Out:
            def __init__(self, i):
                self.i = i

            def __array__(self, *a, **kw):
                ent.set()
                assert rel.wait(60), f"batch {k} was never released"
                if k in owner.fail_fetch:
                    raise RuntimeError(f"read-back of batch {k} failed")
                if not outs:
                    outs["v"] = compute(owner, images, im_info)
                if self.i == 3:
                    with owner.lock:
                        owner.log.append(("rP", k))
                    got.set()       # device_get fetches the four in order
                return np.asarray(outs["v"][self.i])

        return Out(0), Out(1), Out(2), Out(3), None


def held_engine(cfg, B=2, **kw):
    pred = HeldOutputs(cfg, **kw)
    engine = ServeEngine(pred, cfg, ServeOptions(
        batch_size=B, max_delay_ms=1.0, max_queue=32))
    pred.engine = engine
    return engine, pred


def wait_for(cond, limit_s=30.0):
    deadline = time.monotonic() + limit_s
    while not cond():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.005)
    return True


def images_of(values):
    return [raw_image(60, 100, v) for v in values]


# -- (a) the order of the two halves, and the depth ------------------------


def test_batch_k1_is_launched_before_batch_k_is_read_back_and_never_a_third():
    cfg = tiny_cfg()
    engine, pred = held_engine(cfg)
    imgs = images_of(range(20, 180, 20))             # 8 -> four batches
    futs = [engine.submit(im) for im in imgs]         # pre-start: queued
    engine.start()
    try:
        for k in range(4):
            # the turn is inside batch k's read-back, which is held ...
            assert pred.in_read_back(k)
            launched = min(k + 2, 4)
            # ... and batch k+1 is already launched; no third one is, however
            # long the read-back of k takes
            assert wait_for(lambda: pred.launched() == launched)
            time.sleep(0.15)
            assert pred.launched() == launched
            assert not any(f.done() for f in futs[2 * k:])
            pred.release[k].set()
            for f in futs[2 * k:2 * k + 2]:
                assert f.result(timeout=30) is not None
        wait_booked(engine)
        counters = dict(engine.counters)
        assert engine._inflight == 0
    finally:
        pred.release_all()
        engine.stop()
    # at a launch: this batch and at most the one before it
    assert pred.inflight_at_launch == [1, 2, 2, 2]
    # and whatever came before those two had been read back in full
    for k, fetched in enumerate(pred.fetched_at_launch):
        assert all(fetched[:max(k - 1, 0)]), (k, fetched)
        if k:
            assert not fetched[k - 1]       # launched BEFORE k-1's read-back
    assert counters["batches"] == 4 and counters["served"] == 8
    assert counters["overlapped_turns"] == 3
    for im, f in zip(imgs, futs):
        assert f.result(timeout=1) == alone(cfg, im)


# -- (b) the same records as a serial run, both forward paths --------------


def _engine_of(path, cfg):
    stub = FakePredictor if path == "legacy" else FusedStub
    return ServeEngine(stub(cfg), cfg, ServeOptions(
        batch_size=2, max_delay_ms=1.0, max_queue=32,
        serve_e2e=path == "e2e"))


@pytest.mark.parametrize("path", ["legacy", "e2e"])
def test_a_pipelined_run_answers_what_serial_dispatch_batch_answers(path):
    cfg = tiny_cfg()
    rng = np.random.RandomState(3)
    shapes = [(60, 100)] * 7 + [(100, 60)] * 4        # 4 + 2 batches,
    order = rng.permutation(len(shapes))              # one of them partial
    imgs = [rng.randint(0, 255, shapes[i] + (3,), dtype=np.uint8)
            for i in order]

    pipelined = _engine_of(path, cfg)
    futs = [pipelined.submit(im) for im in imgs]      # pre-start: queued
    pipelined.start()
    try:
        got = [f.result(timeout=30) for f in futs]
        wait_booked(pipelined)
        counters = dict(pipelined.counters)
    finally:
        pipelined.stop()
    assert counters["batches"] == 6 and counters["served"] == len(imgs)
    assert counters["overlapped_turns"] == 5          # all but the first
    assert counters["dispatches"] == counters["readbacks"] == 6

    serial = _engine_of(path, cfg).start(external=True)
    try:
        futs = [serial.submit(im) for im in imgs]
        turns = 0
        while True:
            batch, _ = serial.poll(time.monotonic() + 1.0)   # all are due
            if batch is None:
                break
            serial.dispatch_batch(batch)
            assert serial._inflight == 0
            turns += 1
        want = [f.result(timeout=1) for f in futs]
        assert serial.counters["overlapped_turns"] == 0
        assert serial.hists["serve/service_time"].count == turns == 6
    finally:
        serial.stop()
    assert got == want and all(len(r) == 1 for r in got)
    if path == "legacy":
        for im, dets in zip(imgs, got):
            assert dets == alone(cfg, im)


# -- (c) a failure stays with its own batch --------------------------------


@pytest.mark.parametrize("where,failing", [("finish", 0), ("finish", 1),
                                           ("launch", 1), ("launch", 0)])
def test_a_failure_in_one_half_fails_that_batch_only(where, failing):
    cfg = tiny_cfg()
    kw = ({"fail_fetch": [failing]} if where == "finish"
          else {"fail_predict": [failing]})
    engine, pred = held_engine(cfg, held=False, **kw)
    imgs = images_of((30, 70, 110, 150, 190, 230))   # three batches
    futs = [engine.submit(im) for im in imgs]
    engine.start()
    try:
        for k in range(3):
            for f, im in zip(futs[2 * k:2 * k + 2], imgs[2 * k:2 * k + 2]):
                if k == failing:
                    with pytest.raises(RuntimeError, match=f"batch {k}"):
                        f.result(timeout=30)
                else:
                    assert f.result(timeout=30) == alone(cfg, im)
        wait_booked(engine)
        assert engine._inflight == 0
        assert engine.counters["batches"] == 2
        assert engine.counters["served"] == 4
        assert engine.counters["overlapped_turns"] <= 2
        # nothing is left held: the engine serves on, from the same four
        # staging batches
        again = engine.submit(imgs[0]).result(timeout=30)
        wait_booked(engine)
        assert again == alone(cfg, imgs[0])
        assert engine.counters["staging_allocs"] == 4
        with engine._lock:
            free = sum(len(f) for f in engine._staging_free.values())
        assert free == 4
    finally:
        engine.stop()


# -- (d) the buffer-return rule with two in flight -------------------------


def test_with_two_in_flight_no_staging_batch_returns_before_its_own_read_back():
    cfg = tiny_cfg()
    B = 2
    engine, pred = held_engine(cfg, B=B)
    first = images_of((20, 60, 100, 140))            # two batches
    futs = [engine.submit(im) for im in first]
    engine.start()
    try:
        assert pred.in_read_back(0)
        assert wait_for(lambda: pred.launched() == 2)
        in_flight = [pred.buffers[0], pred.buffers[1]]
        assert not np.shares_memory(*in_flight)
        held = [np.array(b) for b in in_flight]
        # further requests arrive while both are held: they fill other
        # staging batches, never one of the two in flight
        later = images_of((180, 200, 220, 240))
        later_futs = [engine.submit(im) for im in later]

        def others():
            with engine._lock:
                return ([r.staging for q in engine._queues.values()
                         for r in q]
                        + [s for f in engine._staging_free.values()
                           for s in f])

        assert len(others()) >= 2 * B
        for s in others():
            assert not any(np.shares_memory(s.images, b) for b in in_flight)
        # batch 0 is read back and finished: ITS staging batch returns
        # (to the free list, or straight into the line); batch 1's, launched
        # long before, does not — its own read-back has not returned
        pred.release[0].set()
        for f in futs[:B]:
            f.result(timeout=30)
        assert pred.in_read_back(1)
        assert wait_for(lambda: any(
            np.shares_memory(s.images, in_flight[0]) for s in others()))
        time.sleep(0.1)
        for s in others():
            assert not np.shares_memory(s.images, in_flight[1])
        assert np.array_equal(in_flight[1], held[1])
        assert not any(f.done() for f in futs[B:])
        pred.release_all()
        results = [f.result(timeout=30) for f in futs + later_futs]
        wait_booked(engine)
        assert engine.counters["staging_allocs"] == 4
    finally:
        pred.release_all()
        engine.stop()
    for im, dets in zip(first + later, results):
        assert dets == alone(cfg, im)


# -- (e) a lone request ----------------------------------------------------


def test_a_lone_request_is_answered_alone_and_idle_never_holds_a_flight():
    cfg = tiny_cfg()
    engine = ServeEngine(FakePredictor(cfg, delay_s=0.01), cfg, ServeOptions(
        batch_size=4, max_delay_ms=1.0, max_queue=32))
    stage = engine._stage
    at_idle = []

    def watched(name, *a, **kw):
        if name == "serve/idle":
            at_idle.append(engine._inflight)   # under the engine's lock
        return stage(name, *a, **kw)

    engine._stage = watched
    engine.start()
    try:
        for v in range(30, 150, 10):            # a lone sequential client
            im = raw_image(60, 100, v)
            assert engine.submit(im).result(timeout=30) == alone(cfg, im)
        wait_booked(engine)
        lone = dict(engine.counters)
        turns = engine.hists["serve/service_time"].count
        # then a burst, so that flights do overlap in this same engine
        futs = [engine.submit(raw_image(60, 100, 20 + v)) for v in range(24)]
        for f in futs:
            f.result(timeout=30)
        wait_booked(engine)
        burst = dict(engine.counters)
    finally:
        engine.stop()
    assert lone["batches"] == lone["served"] == 12 == turns
    assert lone["overlapped_turns"] == 0
    assert burst["overlapped_turns"] >= 1
    assert burst["overlapped_turns"] <= burst["batches"] - lone["batches"]
    assert len(at_idle) >= 12 and not any(at_idle)


# -- (f) drain and stop with a batch in flight -----------------------------


@pytest.mark.parametrize("how", ["drain", "stop"])
def test_drain_and_stop_return_after_the_flights_are_answered(how):
    cfg = tiny_cfg()
    engine, pred = held_engine(cfg)
    imgs = images_of((40, 80, 120, 160))             # two batches
    futs = [engine.submit(im) for im in imgs]
    engine.start()
    out = {}
    call = threading.Thread(
        target=lambda: out.setdefault(
            "v", engine.drain(timeout=30) if how == "drain"
            else engine.stop(timeout=30)), daemon=True)
    try:
        assert pred.in_read_back(0)
        assert wait_for(lambda: pred.launched() == 2)  # two in flight
        call.start()
        time.sleep(0.2)
        assert call.is_alive()                  # it waits for the flights
        assert not any(f.done() for f in futs)
        with engine._lock:
            assert engine._inflight == 2
        if how == "drain":
            with pytest.raises(RejectedError, match="draining"):
                engine.submit(imgs[0])
        pred.release_all()
        call.join(30)
        assert not call.is_alive()
        assert engine._inflight == 0
        # both were answered, not failed, before the call returned
        assert all(f.done() for f in futs)
        for im, f in zip(imgs, futs):
            assert f.result(timeout=1) == alone(cfg, im)
        if how == "drain":
            assert out["v"] is True
            engine.resume()
            assert engine.submit(imgs[1]).result(timeout=30) == alone(
                cfg, imgs[1])
    finally:
        pred.release_all()
        engine.stop()


# -- (g) a mask network: each batch's masks over its own pyramid -----------


@pytest.fixture(scope="module")
def mask_model():
    import jax

    from mx_rcnn_tpu.eval import Predictor
    from mx_rcnn_tpu.models import build_model, init_params
    from tests.test_serve_masks import mask_cfg

    cfg = mask_cfg()
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0), 2, (64, 96))
    return cfg, Predictor(model, params, cfg)


def test_with_two_in_flight_each_batchs_masks_are_the_serial_runs(mask_model):
    cfg, pred = mask_model
    rng = np.random.default_rng(21)
    imgs = [rng.integers(0, 256, (50 + 2 * i, 70 + 3 * i, 3), dtype=np.uint8)
            for i in range(8)]                        # four batches of two
    opts = ServeOptions(batch_size=2, max_delay_ms=1.0, max_queue=16)

    serial = ServeEngine(pred, cfg, opts).start(external=True)
    try:
        futs = [serial.submit(im) for im in imgs]
        while True:
            batch, _ = serial.poll(time.monotonic() + 1.0)
            if batch is None:
                break
            serial.dispatch_batch(batch)
        want = [f.result(timeout=1) for f in futs]
        serial_counters = dict(serial.counters)
    finally:
        serial.stop()
    assert serial_counters["overlapped_turns"] == 0

    pipelined = ServeEngine(pred, cfg, opts)
    futs = [pipelined.submit(im) for im in imgs]      # pre-start: queued
    pipelined.start()
    try:
        got = [f.result(timeout=300) for f in futs]
        wait_booked(pipelined)
        counters = dict(pipelined.counters)
        stages = pipelined.metrics()["stages"]
    finally:
        pipelined.stop()
    assert counters["batches"] == 4 and counters["overlapped_turns"] == 3
    # one mask program a batch (one more where score ties leave an image
    # over its cap), as in the serial run
    assert counters["mask_dispatches"] == \
        serial_counters["mask_dispatches"] >= 4
    assert got == want                 # boxes, scores and every count list
    assert sum(len(r) for r in got) == counters["mask_rois"] > 0
    assert all("segmentation" in r for recs in got for r in recs)
    # the batches differ, so a mask over the wrong pyramid would show
    assert len({str(recs) for recs in got}) == len(got)
    # the turn still covers both stages of every batch
    assert stages["serve/service_time"]["count"] == 4
    assert stages["serve/service_time"]["sum_s"] >= (
        stages["serve/forward"]["sum_s"] + stages["serve/readback"]["sum_s"]
        + stages["serve/postprocess"]["sum_s"] + stages["serve/mask"]["sum_s"])


# -- (h) the counter and the clocks under many threads ---------------------


def test_overlapped_turns_and_the_dispatchers_clocks_stay_inside_the_run():
    import sys

    cfg = tiny_cfg()
    n_threads, per_thread = 16, 8
    t0 = time.monotonic()
    engine = ServeEngine(FakePredictor(cfg, delay_s=0.003), cfg, ServeOptions(
        batch_size=4, max_delay_ms=2.0, max_queue=64)).start()
    wrong = []
    start = threading.Barrier(n_threads)

    def client(t):
        start.wait()
        for i in range(per_thread):
            im = raw_image(60, 100, 10 + (t * 11 + i * 17) % 240)
            dets = engine.submit(im).result(timeout=60)
            if len(dets) != 1:
                wrong.append((t, i, dets))

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
        assert engine.drain(timeout=30)
        assert engine._inflight == 0
        wall = time.monotonic() - t0
        m = engine.metrics()
    finally:
        sys.setswitchinterval(interval)
        engine.stop()
    assert not alive and not wrong, (alive, wrong[:3])
    c, s = m["counters"], m["stages"]
    assert c["served"] == c["requests"] == n_threads * per_thread
    assert 0 < c["overlapped_turns"] <= c["batches"]
    assert s["serve/service_time"]["count"] == c["batches"]
    # the dispatcher thread is in a turn or idle, never both: its two
    # clocks cannot outlast the run (dispatcher_busy cannot pass 100 %)
    assert (s["serve/service_time"]["sum_s"] + s["serve/idle"]["sum_s"]
            <= wall)
    # and the stages of both halves lie inside the turns
    assert (s["serve/assemble"]["sum_s"] + s["serve/forward"]["sum_s"]
            + s["serve/readback"]["sum_s"] + s["serve/postprocess"]["sum_s"]
            <= s["serve/service_time"]["sum_s"])


# -- (i) a mask network: the mask stage's tail, a turn later ----------------


def mask_tiny_cfg():
    import dataclasses

    cfg = tiny_cfg()
    return cfg.replace(network=dataclasses.replace(cfg.network,
                                                   HAS_MASK=True))


class HeldMasks(HeldOutputs):
    """``HeldOutputs`` on a mask network.  ``capture_feats`` hands out a copy
    of the batch's images as its pyramid; the mask program of batch ``k``
    returns probabilities that are a function of that pyramid's rows and of
    the boxes, fetchable only once ``mask_release[k]`` is set.  The log gains
    ("M", k) at each mask dispatch and ("rM", k) where its read-back
    begins; ``fail_mask``: batch numbers whose mask read-back raises."""

    def __init__(self, cfg, held_masks=True, fail_mask=(), **kw):
        super().__init__(cfg, **kw)
        self.held_masks, self.fail_mask = held_masks, set(fail_mask)
        self.mask_release = {}

    def release_all(self):
        super().release_all()
        with self.lock:
            self.held_masks = False
            for ev in self.mask_release.values():
                ev.set()

    def in_mask_read_back(self, k, limit_s=30.0):
        return wait_for(lambda: ("rM", k) in self.log, limit_s)

    def capture_feats(self):
        with self.lock:
            k = len(self.buffers) - 1
            return (k, np.array(self.buffers[k])), k

    @staticmethod
    def masks_shape(boxes_shape, feats):
        return tuple(boxes_shape) + (7, 7)

    def predict_masks_cached(self, boxes, labels, token, feats):
        k, images = feats
        owner = self
        with self.lock:
            rel = self.mask_release.setdefault(k, threading.Event())
            if not self.held_masks:
                rel.set()
            self.log.append(("M", k))
        ramp = np.linspace(0.0, 1.0, 28, dtype=np.float32)

        class MaskOut:
            def __array__(self, *a, **kw):
                with owner.lock:
                    owner.log.append(("rM", k))
                assert rel.wait(60), f"mask of batch {k} was never released"
                if k in owner.fail_mask:
                    raise RuntimeError(f"mask read-back of batch {k} failed")
                # each row's map from its own image and boxes: a mask over
                # another batch's pyramid would differ
                v = np.asarray([owner.row_score(im) for im in images],
                               np.float32)[:, None, None, None]
                w = (np.asarray(boxes).sum(-1) % 7.0)[..., None, None] / 7.0
                return np.clip(ramp[None, None, None, :] + v - 0.5 + 0.1 * w
                               + np.zeros((28, 1), np.float32), 0.0, 1.0)

        return MaskOut()


def held_mask_engine(B=2, **kw):
    cfg = mask_tiny_cfg()
    pred = HeldMasks(cfg, **kw)
    engine = ServeEngine(pred, cfg, ServeOptions(
        batch_size=B, max_delay_ms=1.0, max_queue=32))
    pred.engine = engine
    return engine, pred


def boxes_only(recs):
    return [{k: v for k, v in r.items() if k != "segmentation"}
            for r in recs]


def test_on_a_mask_network_mask_k_is_read_back_after_predict_k1_never_behind():
    engine, pred = held_mask_engine()
    imgs = images_of(range(20, 180, 20))             # 8 -> four batches
    futs = [engine.submit(im) for im in imgs]         # pre-start: queued
    engine.start()
    try:
        for k in range(4):
            # inside predict k's read-back: batch k-1's mask program is
            # dispatched and not read back, its requests not answered
            assert pred.in_read_back(k)
            if k:
                assert ("M", k - 1) in pred.log
                assert ("rM", k - 1) not in pred.log
                assert not any(f.done() for f in futs[2 * (k - 1):])
            pred.release[k].set()
            if k:
                # the tail of k-1 comes right after predict k's read-back
                assert pred.in_mask_read_back(k - 1)
                time.sleep(0.05)
                assert not any(f.done() for f in futs[2 * (k - 1):])
                pred.mask_release[k - 1].set()
                for f in futs[2 * (k - 1):2 * k]:
                    assert f.result(timeout=30)[0]["segmentation"]
        # nothing else is due: the last batch's tail follows its finish
        assert pred.in_mask_read_back(3)
        pred.mask_release[3].set()
        results = [f.result(timeout=30) for f in futs]
        wait_booked(engine)
        counters = dict(engine.counters)
        assert engine._inflight == 0
    finally:
        pred.release_all()
        engine.stop()
    log = pred.log
    # on the device, P0 P1 M0 P2 M1 P3 M2 M3: each mask program behind the
    # predict launched before its dispatch, ahead of the next one
    assert [e for e in log if e[0] in "PM"] == [
        ("P", 0), ("P", 1), ("M", 0), ("P", 2), ("M", 1), ("P", 3),
        ("M", 2), ("M", 3)]
    for k in range(4):
        at = log.index(("rM", k))
        ahead = [j for kind, j in log[:log.index(("M", k))] if kind == "P"]
        # every predict ahead of mask k on the device is read back before
        # mask k's read-back begins, predict k+1's among them
        assert all(log.index(("rP", j)) < at for j in ahead), (k, log)
        if k < 3:
            assert log.index(("rP", k + 1)) < at
    # a batch keeps its slot until its tail: three at a launch
    assert pred.inflight_at_launch == [1, 2, 3, 3]
    assert counters["batches"] == 4 and counters["served"] == 8
    assert counters["overlapped_turns"] == 3
    assert counters["deferred_masks"] == 3            # all but the last
    assert counters["mask_dispatches"] == 4
    for im, recs in zip(imgs, results):
        assert boxes_only(recs) == alone(tiny_cfg(), im)


@pytest.mark.parametrize("network", ["mask", "box"])
def test_a_saturated_run_defers_every_mask_but_the_last_and_serial_none(
        network):
    """Four batches queued ahead: a mask network defers the tails of all
    but the last (the serial ``dispatch_batch`` run of none) and answers
    what the serial run answers, count list for count list; a box network
    defers nothing."""
    cfg = mask_tiny_cfg() if network == "mask" else tiny_cfg()
    opts = ServeOptions(batch_size=2, max_delay_ms=1.0, max_queue=32)
    imgs = images_of((25, 55, 85, 115, 145, 175, 205, 235))

    def stub():
        return (HeldMasks(cfg, held=False, held_masks=False)
                if network == "mask" else FakePredictor(cfg))

    pipelined = ServeEngine(stub(), cfg, opts)
    futs = [pipelined.submit(im) for im in imgs]      # pre-start: queued
    pipelined.start()
    try:
        got = [f.result(timeout=30) for f in futs]
        wait_booked(pipelined)
        counters = dict(pipelined.counters)
        metrics = pipelined.metrics()
    finally:
        pipelined.stop()

    serial = ServeEngine(stub(), cfg, opts).start(external=True)
    try:
        futs = [serial.submit(im) for im in imgs]
        while True:
            batch, _ = serial.poll(time.monotonic() + 1.0)
            if batch is None:
                break
            serial.dispatch_batch(batch)
            assert serial._inflight == 0
        want = [f.result(timeout=1) for f in futs]
        serial_counters = dict(serial.counters)
    finally:
        serial.stop()
    assert counters["batches"] == 4 and counters["overlapped_turns"] == 3
    assert counters["deferred_masks"] == (3 if network == "mask" else 0)
    assert metrics["counters"]["deferred_masks"] == counters["deferred_masks"]
    assert serial_counters["deferred_masks"] == 0
    assert got == want and all(len(r) == 1 for r in got)
    if network == "mask":
        assert all(r[0]["segmentation"]["counts"] for r in got)
        # the maps differ by image: a mask of another row would show
        assert len({str(r[0]["segmentation"]) for r in got}) == len(got)
        for k in ("mask_dispatches", "mask_rois", "dispatches", "readbacks",
                  "readback_bytes", "h2d_bytes", "post_kept"):
            assert counters[k] == serial_counters[k], k
        stages = metrics["stages"]
        for name in ("serve/mask", "serve/mask/forward",
                     "serve/mask/readback", "serve/mask/paste",
                     "serve/service_time"):
            assert stages[name]["count"] == 4, name
        parts = sum(stages[n]["sum_s"] for n in (
            "serve/mask/forward", "serve/mask/readback", "serve/mask/paste"))
        # the dispatcher's seconds in the stage, not the wall across turns
        assert parts <= stages["serve/mask"]["sum_s"] <= \
            stages["serve/service_time"]["sum_s"]


def test_a_lone_request_on_a_mask_network_is_answered_without_another():
    engine, pred = held_mask_engine(B=4, held=False, held_masks=False)
    engine.start()
    try:
        for v in (40, 130, 220):                # one at a time
            im = raw_image(60, 100, v)
            recs = engine.submit(im).result(timeout=30)
            assert boxes_only(recs) == alone(tiny_cfg(), im)
            assert recs[0]["segmentation"]["counts"]
        wait_booked(engine)
        c = dict(engine.counters)
    finally:
        engine.stop()
    assert c["batches"] == c["served"] == c["mask_dispatches"] == 3
    assert c["deferred_masks"] == 0 and c["overlapped_turns"] == 0


@pytest.mark.parametrize("how", ["drain", "stop"])
def test_drain_and_stop_answer_a_pending_mask_tail(how):
    engine, pred = held_mask_engine()
    imgs = images_of((40, 80, 120, 160))             # two batches
    futs = [engine.submit(im) for im in imgs]
    engine.start()
    out = {}
    call = threading.Thread(
        target=lambda: out.setdefault(
            "v", engine.drain(timeout=30) if how == "drain"
            else engine.stop(timeout=30)), daemon=True)
    try:
        assert pred.in_read_back(0)
        assert wait_for(lambda: pred.launched() == 2)
        pred.release[0].set()
        # batch 0's mask program dispatched, its tail pending behind the
        # read-back of batch 1, which is held
        assert pred.in_read_back(1)
        assert wait_for(lambda: ("M", 0) in pred.log)
        call.start()
        time.sleep(0.2)
        assert call.is_alive()                  # it waits for the tail
        assert not any(f.done() for f in futs)
        with engine._lock:
            assert engine._inflight == 2
        pred.release_all()
        call.join(30)
        assert not call.is_alive()
        assert engine._inflight == 0
        assert all(f.done() for f in futs)
        for im, f in zip(imgs, futs):
            recs = f.result(timeout=1)
            assert boxes_only(recs) == alone(tiny_cfg(), im)
            assert recs[0]["segmentation"]["counts"]
        assert engine.counters["batches"] == 2
        assert engine.counters["deferred_masks"] == 1
        if how == "drain":
            assert out["v"] is True
    finally:
        pred.release_all()
        engine.stop()


@pytest.mark.parametrize("failing", [0, 1, 2])
def test_a_failure_in_a_mask_tail_fails_that_batch_only(failing):
    engine, pred = held_mask_engine(held=False, held_masks=False,
                                    fail_mask=[failing])
    imgs = images_of((30, 70, 110, 150, 190, 230))   # three batches
    futs = [engine.submit(im) for im in imgs]
    engine.start()
    try:
        for k in range(3):
            for f, im in zip(futs[2 * k:2 * k + 2], imgs[2 * k:2 * k + 2]):
                if k == failing:
                    with pytest.raises(RuntimeError,
                                       match=f"mask read-back of batch {k}"):
                        f.result(timeout=30)
                else:
                    recs = f.result(timeout=30)
                    assert boxes_only(recs) == alone(tiny_cfg(), im)
                    assert recs[0]["segmentation"]["counts"]
        wait_booked(engine)
        assert engine._inflight == 0
        assert engine.counters["batches"] == 2
        assert engine.counters["served"] == 4
        # nothing is left held: the engine serves on, from the same four
        # staging batches
        again = engine.submit(imgs[0]).result(timeout=30)
        wait_booked(engine)
        assert boxes_only(again) == alone(tiny_cfg(), imgs[0])
        assert engine.counters["staging_allocs"] == 4
        with engine._lock:
            free = sum(len(f) for f in engine._staging_free.values())
        assert free == 4
    finally:
        engine.stop()
