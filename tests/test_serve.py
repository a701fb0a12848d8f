"""Serving subsystem tier-1 tests (CPU).

Batcher mechanics (bucket routing, partial-batch padding + response
unmasking, full-beats-partial flush ordering, backpressure, per-request
deadlines) run against a shape-faithful fake predictor — no model, no
compile.  One end-to-end test runs the real thing: tiny synthetic-weight
model, warmup, Unix-socket HTTP round trip, zero post-warmup recompiles
(telemetry counter assert), and byte-parity between served detections
and the offline Predictor + shared-postprocess path.
"""

import dataclasses
import io
import json
import threading
import time

import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.data import prepare_image
from mx_rcnn_tpu.ops.nms import nms as numpy_nms
from mx_rcnn_tpu.ops.postprocess import (decode_image_boxes,
                                         detections_to_records,
                                         per_class_nms)
from mx_rcnn_tpu.serve import (DeadlineExceededError, RejectedError,
                               ServeEngine, ServeOptions,
                               encode_image_payload, make_server, run_stdio,
                               unix_http_request, warmup)


def tiny_cfg():
    cfg = generate_config(
        "resnet50", "PascalVOC",
        TEST__RPN_PRE_NMS_TOP_N=300, TEST__RPN_POST_NMS_TOP_N=32,
    )
    net = dataclasses.replace(cfg.network, ANCHOR_SCALES=(2, 4))
    tpu = dataclasses.replace(cfg.tpu, SCALES=((96, 128),), MAX_GT=8)
    return cfg.replace(network=net, tpu=tpu)


class FakePredictor:
    """Shape-faithful Predictor stub.  One valid roi per row, scored by a
    smooth function of the row's mean activation — so a response's score
    identifies WHICH image filled its batch row, and the padding/unmasking
    tests read the row→request mapping straight off the detections."""

    R = 4

    def __init__(self, cfg, delay_s=0.0):
        self.cfg = cfg
        self.delay_s = delay_s
        self.batches = []  # input shape of every forward, in order

    @staticmethod
    def row_score(prepared):
        # bounded well inside (TEST.THRESH, 1), distinct for distinct means
        return float(np.tanh(np.asarray(prepared, np.float64).mean() / 100)
                     * 0.4 + 0.5)

    def predict(self, images, im_info):
        if self.delay_s:
            time.sleep(self.delay_s)
        images = np.asarray(images)
        self.batches.append(tuple(images.shape))
        B, (R, K) = images.shape[0], (self.R, self.cfg.NUM_CLASSES)
        rois = np.zeros((B, R, 4), np.float32)
        rois[:, :, 2:] = 16.0
        valid = np.zeros((B, R), bool)
        valid[:, 0] = True
        scores = np.zeros((B, R, K), np.float32)
        for b in range(B):
            scores[b, 0, 1] = self.row_score(images[b])
        deltas = np.zeros((B, R, 4 * K), np.float32)
        return rois, valid, scores, deltas, None


def make_engine(cfg, **opts):
    defaults = dict(batch_size=4, max_delay_ms=1.0, max_queue=16)
    defaults.update(opts)
    return ServeEngine(FakePredictor(cfg), cfg, ServeOptions(**defaults))


def raw_image(h, w, value):
    return np.full((h, w, 3), value, np.uint8)


# -- shared postprocess ----------------------------------------------------


def test_per_class_nms_thresh_valid_and_cap():
    R, K = 5, 3
    scores = np.zeros((R, K), np.float32)
    scores[:, 1] = [0.9, 0.8, 0.002, 0.0005, 0.7]
    boxes = np.zeros((R, 4 * K), np.float32)
    for i in range(R):  # well-separated boxes: NMS never merges them
        boxes[i, 4:8] = [i * 30, 0, i * 30 + 10, 10]
    valid = np.array([1, 1, 1, 1, 0], bool)

    dets = per_class_nms(scores, boxes, valid, K, thresh=1e-3,
                         nms_thresh=0.3, max_per_image=0)
    # row 3 under thresh, row 4 (0.7) invalid; class 2 has no scores at all
    assert len(dets[1]) == 3 and len(dets[2]) == 0
    assert sorted(dets[1][:, 4]) == [np.float32(0.002), np.float32(0.8),
                                     np.float32(0.9)]

    capped = per_class_nms(scores, boxes, valid, K, thresh=1e-3,
                           nms_thresh=0.3, max_per_image=2)
    assert len(capped[1]) == 2
    assert sorted(capped[1][:, 4]) == [np.float32(0.8), np.float32(0.9)]

    recs = detections_to_records(dets)
    assert [r["cls"] for r in recs] == [1, 1, 1]
    assert [r["score"] for r in recs] == sorted(
        (r["score"] for r in recs), reverse=True)
    assert len(recs[0]["bbox"]) == 4


# -- batcher mechanics (fake predictor, engine not necessarily started) ----


def test_bucket_routing_two_orientations():
    cfg = tiny_cfg()
    engine = make_engine(cfg)
    # orientation picks the bucket: transposed shapes
    land, port = engine.bucket_key(60, 100), engine.bucket_key(100, 60)
    assert land == (port[1], port[0])
    # not started: submissions park in their queues for inspection
    engine.submit(raw_image(60, 100, 50))
    engine.submit(raw_image(100, 60, 50))
    engine.submit(raw_image(50, 90, 50))  # another landscape
    m = engine.metrics()
    assert m["queue_depth"] == 3
    assert m["buckets"] == {f"{land[0]}x{land[1]}": 2,
                            f"{port[0]}x{port[1]}": 1}
    fut = engine.submit(raw_image(60, 100, 50))
    engine.stop()  # fails whatever is still queued
    try:
        fut.result(timeout=5)
        raise AssertionError("stopped engine should fail pending futures")
    except RejectedError:
        pass


def test_partial_batch_padded_and_responses_unmasked():
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=4, max_delay_ms=1.0)
    fake = engine.predictor
    values = (40, 120, 200)
    imgs = [raw_image(60, 100, v) for v in values]
    futs = [engine.submit(im) for im in imgs]  # pre-start: deterministic
    engine.start()
    try:
        results = [f.result(timeout=30) for f in futs]
    finally:
        engine.stop()
    # one forward of the whole staging batch: three live rows and one that
    # nobody wrote (tests/test_serve_staging.py holds what the rows hold)
    assert len(fake.batches) == 1 and fake.batches[0][0] == 4
    # each response carries ITS OWN image's score — row→request mapping
    # survives the padding (and the padding row produces nothing)
    for img, dets in zip(imgs, results):
        prepared, _ = prepare_image(img, cfg, cfg.tpu.SCALES[0])
        assert len(dets) == 1
        assert abs(dets[0]["score"] - fake.row_score(prepared)) < 1e-5
    assert engine.counters["served"] == 3
    assert engine.counters["batches"] == 1


@pytest.mark.parametrize("library", ["loaded", "missing"])
def test_post_nms_native_counts_the_images_the_library_served(
        library, monkeypatch):
    """``post_nms_native`` beside ``served``: equal with the native library
    (every image's per-class NMS was its one call), 0 without it — and the
    records are the same either way."""
    from mx_rcnn_tpu import native

    if library == "missing":
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_load", lambda: None)
    assert native.available("mxr_nms_classes") == (library == "loaded")
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=4, max_delay_ms=1.0)
    imgs = [raw_image(60, 100, v) for v in (40, 120, 200)] \
        + [raw_image(100, 60, 80)]
    futs = [engine.submit(im) for im in imgs]
    engine.start()
    try:
        results = [f.result(timeout=30) for f in futs]
        counters = engine.metrics()["counters"]
    finally:
        engine.stop()
    assert counters["served"] == 4
    assert counters["post_nms_native"] == (4 if library == "loaded" else 0)
    # the records: the loop over the numpy NMS on the same read-back
    for img, dets in zip(imgs, results):
        prepared, im_info = prepare_image(img, cfg, cfg.tpu.SCALES[0])
        rois, valid, scores, deltas, _ = engine.predictor.predict(
            prepared[None], im_info[None])
        assert dets == detections_to_records(per_class_nms(
            scores[0], decode_image_boxes(rois[0], deltas[0], im_info),
            valid[0], cfg.NUM_CLASSES, cfg.TEST.THRESH, cfg.TEST.NMS,
            cfg.TEST.MAX_PER_IMAGE, nms_fn=numpy_nms))
        assert len(dets) == 1


def test_pyramid_network_served_by_the_native_call_equals_the_numpy_loop():
    """The second benchmark configuration's served path at a tiny size
    (ResNet-50-FPN, a 128x192 bucket, 500 -> 60 proposals, batch 2):
    every image's per-class NMS is the one native call
    (``post_nms_native`` == ``served``), the records are those of the
    Python loop over the numpy NMS on the same read-back, and the level
    counters of ``_roi_level_counts`` add up to ``rois_valid``."""
    import jax

    from mx_rcnn_tpu import native
    from mx_rcnn_tpu.eval import Predictor
    from mx_rcnn_tpu.models import build_model, init_params
    from mx_rcnn_tpu.train.checkpoint import denormalize_for_save

    assert native.available("mxr_nms_classes")
    cfg = generate_config(
        "resnet50_fpn", "coco", tpu__SCALES=((128, 192),),
        TEST__RPN_PRE_NMS_TOP_N=500, TEST__RPN_POST_NMS_TOP_N=60)
    assert cfg.network.HAS_FPN
    model = build_model(cfg)
    params = denormalize_for_save(
        init_params(model, cfg, jax.random.PRNGKey(0), 2, (128, 192)), cfg)
    pred = Predictor(model, params, cfg)
    engine = ServeEngine(pred, cfg, ServeOptions(
        batch_size=2, max_delay_ms=1.0, max_queue=16)).start()
    rng = np.random.RandomState(11)
    images = [rng.randint(0, 255, (h, w, 3), dtype=np.uint8)
              for h, w in ((80, 120), (64, 100), (72, 96))]
    try:
        # one at a time: each batch is the image in row 0 and, in the
        # padding row, whatever the staging batch held; the model is
        # per-image, so the offline call below (the image and its own
        # copy) gives row 0 the same answer
        served = [engine.submit(img).result(timeout=600) for img in images]
        # a turn books its counters after it has set its answers
        deadline = time.monotonic() + 30
        while (engine.metrics()["counters"]["served"] < 3
               and time.monotonic() < deadline):
            time.sleep(0.01)
        counters = engine.metrics()["counters"]
    finally:
        engine.stop()

    assert counters["served"] == counters["post_nms_native"] == 3
    assert counters["rois_valid"] > 0
    assert counters["rois_valid"] == sum(
        counters[f"rois_level_p{lvl}"] for lvl in (2, 3, 4, 5))
    assert counters["post_candidates"] > counters["post_kept"] > 0
    valid_seen = 0
    for img, dets in zip(images, served):
        prepared, im_info = prepare_image(img, cfg, cfg.tpu.SCALES[0])
        rois, valid, scores, deltas, _ = [
            np.asarray(jax.device_get(x)) for x in pred.predict(
                np.stack([prepared, prepared]),
                np.stack([im_info, im_info]))]
        valid_seen += int(valid[0].sum())
        assert dets == detections_to_records(per_class_nms(
            scores[0], decode_image_boxes(rois[0], deltas[0], im_info),
            valid[0], cfg.NUM_CLASSES, cfg.TEST.THRESH, cfg.TEST.NMS,
            cfg.TEST.MAX_PER_IMAGE, nms_fn=numpy_nms))
        assert dets
    assert counters["rois_valid"] == valid_seen


def test_full_bucket_flushes_before_older_partial():
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=4, max_delay_ms=300.0)
    fake = engine.predictor
    older = engine.submit(raw_image(60, 100, 50))       # landscape, partial
    full = [engine.submit(raw_image(100, 60, 50)) for _ in range(4)]
    engine.start()
    try:
        for f in full:
            f.result(timeout=30)
        older.result(timeout=30)  # flushes at the max-delay deadline
    finally:
        engine.stop()
    land, _ = prepare_image(raw_image(60, 100, 50), cfg, cfg.tpu.SCALES[0])
    port, _ = prepare_image(raw_image(100, 60, 50), cfg, cfg.tpu.SCALES[0])
    # the FULL portrait bucket won the first flush although the landscape
    # request was enqueued first; the partial flushed on its deadline
    assert fake.batches == [(4,) + port.shape, (4,) + land.shape]


def test_backpressure_rejects_when_queue_full():
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=2, max_queue=4)
    for _ in range(4):  # engine not started: nothing drains
        engine.submit(raw_image(60, 100, 50))
    try:
        engine.submit(raw_image(60, 100, 50))
        raise AssertionError("5th submit should be rejected")
    except RejectedError as e:
        assert "queue full" in str(e)
    assert engine.counters["rejected"] == 1
    assert engine.counters["requests"] == 4
    engine.stop()


def test_request_deadline_expires_without_forward():
    cfg = tiny_cfg()
    engine = make_engine(cfg)
    fake = engine.predictor
    fut = engine.submit(raw_image(60, 100, 50), deadline_ms=1.0)
    time.sleep(0.05)  # expire while the engine is not yet draining
    engine.start()
    try:
        try:
            fut.result(timeout=10)
            raise AssertionError("expired request should fail")
        except DeadlineExceededError:
            pass
        assert engine.counters["deadline_exceeded"] == 1
        # the expired request never cost a forward pass
        assert fake.batches == []
    finally:
        engine.stop()


# -- frontends -------------------------------------------------------------


def test_stdio_frontend_statuses():
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=1, max_delay_ms=0.0).start()
    img = raw_image(40, 60, 120)
    inp = io.StringIO("this is not json\n"
                      + json.dumps({"pixels": img.tolist()}) + "\n"
                      + json.dumps({"shape": [2, 2]}) + "\n")
    out = io.StringIO()
    try:
        run_stdio(engine, inp, out)
    finally:
        engine.stop()
    lines = [json.loads(line) for line in out.getvalue().splitlines()]
    assert [d["status"] for d in lines] == [400, 200, 400]
    assert lines[1]["detections"] and "queue_wait_ms" in lines[1]


def test_serve_e2e_unix_socket_warm_and_parity(tmp_path):
    """The whole path on real (synthetic-weight) compute: warmup compiles
    exactly one program per orientation, mixed-size HTTP traffic over a
    Unix socket serves with ZERO further recompiles (telemetry counter
    assert), and the served detections are identical to the offline
    Predictor + shared-postprocess path for the same pixels."""
    import jax

    from mx_rcnn_tpu import telemetry
    from mx_rcnn_tpu.eval import Predictor
    from mx_rcnn_tpu.models import build_model, init_params
    from mx_rcnn_tpu.train.checkpoint import denormalize_for_save

    cfg = tiny_cfg()
    model = build_model(cfg)
    params = denormalize_for_save(
        init_params(model, cfg, jax.random.PRNGKey(0), 2, (96, 128)), cfg)
    pred = Predictor(model, params, cfg)
    engine = ServeEngine(pred, cfg, ServeOptions(
        batch_size=2, max_delay_ms=5.0, max_queue=16)).start()
    telemetry.configure(str(tmp_path / "tel"), run_meta={"driver": "test"})
    sock = str(tmp_path / "serve.sock")
    server = make_server(engine, unix_socket=sock)
    th = threading.Thread(target=server.serve_forever, daemon=True)
    try:
        compiled = warmup(engine)
        assert compiled == 2  # one program per orientation bucket
        th.start()

        status, health = unix_http_request(sock, "GET", "/healthz")
        assert status == 200 and health["status"] == "ok"

        rng = np.random.RandomState(7)
        images = [rng.randint(0, 255, (h, w, 3), dtype=np.uint8)
                  for h, w in ((60, 100), (100, 60), (48, 90), (90, 48))]
        served = []
        for img in images:
            status, resp = unix_http_request(
                sock, "POST", "/predict", encode_image_payload(img),
                timeout=300)
            assert status == 200, resp
            assert "queue_wait_ms" in resp
            served.append(resp["detections"])

        # parity: offline path (Predictor + shared postprocess) on the
        # same pixels — self-padded to the serve batch (the engine's
        # padding rows hold other pixels; row 0's answer is per-image)
        for img, dets in zip(images, served):
            prepared, im_info = prepare_image(img, cfg, cfg.tpu.SCALES[0])
            rois, valid, scores, deltas, _ = [
                np.asarray(jax.device_get(x)) for x in pred.predict(
                    np.stack([prepared, prepared]),
                    np.stack([im_info, im_info]))]
            boxes = decode_image_boxes(rois[0], deltas[0], im_info)
            expect = detections_to_records(per_class_nms(
                scores[0], boxes, valid[0], cfg.NUM_CLASSES,
                cfg.TEST.THRESH, cfg.TEST.NMS, cfg.TEST.MAX_PER_IMAGE))
            assert len(dets) == len(expect)
            for d, e in zip(dets, expect):
                assert d["cls"] == e["cls"]
                assert abs(d["score"] - e["score"]) < 1e-5
                assert np.allclose(d["bbox"], e["bbox"], atol=1e-3)

        # zero recompiles after warmup — the subsystem's core guarantee
        status, m = unix_http_request(sock, "GET", "/metrics")
        assert status == 200
        assert m["counters"]["recompiles"] == m["counters"]["warmup_programs"]
        summ = telemetry.get().summary()
        assert (summ["counters"]["serve/recompile"]
                == summ["counters"]["serve/warmup_programs"] == 2)
        assert "serve/rejected" not in summ["counters"]
        assert summ["spans"]["serve/forward"]["count"] >= 3
    finally:
        if th.is_alive():
            server.shutdown()
        server.server_close()
        engine.stop()
        telemetry.shutdown()


def test_serve_e2e_fused_single_dispatch_contract():
    """``--serve-e2e`` acceptance: warmup registers kind-labeled fused
    programs (one per orientation), a request batch crosses the host↔device
    boundary exactly once in each direction (1 h2d / 1 dispatch /
    1 readback — counter assert), the detection readback is a fraction of
    the legacy fat path's, a hot param swap costs zero recompiles, and
    fused detections match the unfused engine's records at float
    tolerance (exact score ties at the MAX_PER_IMAGE cap may resolve
    differently — the documented device-postprocess divergence)."""
    import jax

    from mx_rcnn_tpu.eval import Predictor
    from mx_rcnn_tpu.models import build_model, init_params
    from mx_rcnn_tpu.train.checkpoint import denormalize_for_save

    cfg = tiny_cfg()
    model = build_model(cfg)
    params = denormalize_for_save(
        init_params(model, cfg, jax.random.PRNGKey(0), 2, (96, 128)), cfg)
    pred = Predictor(model, params, cfg)

    rng = np.random.RandomState(11)
    land_a = rng.randint(0, 255, (60, 100, 3), dtype=np.uint8)
    land_b = rng.randint(0, 255, (48, 90, 3), dtype=np.uint8)
    port = rng.randint(0, 255, (100, 60, 3), dtype=np.uint8)
    images = [land_a, land_b, port]

    # unfused reference on the SAME predictor/registry: the legacy and
    # fused kinds coexist in one program key space
    legacy = ServeEngine(pred, cfg, ServeOptions(
        batch_size=2, max_delay_ms=5.0, max_queue=16)).start()
    try:
        expect = [legacy.submit(img).result(timeout=300) for img in images]
        lc = dict(legacy.counters)
    finally:
        legacy.stop()
    assert lc["h2d_transfers"] == 2 * lc["batches"]  # images + im_info
    legacy_readback_per_batch = lc["readback_bytes"] / lc["batches"]

    engine = ServeEngine(pred, cfg, ServeOptions(
        batch_size=2, max_delay_ms=200.0, max_queue=16,
        serve_e2e=True)).start()
    try:
        assert warmup(engine) == 2  # one fused program per orientation
        # /metrics compile snapshot labels programs by kind: the fused
        # programs are distinguishable from the legacy forwards
        rows = engine.metrics()["compile"]["programs"]
        kinds = {p["kind"] for p in rows}
        assert "serve_e2e" in kinds and "predict" in kinds
        assert sum(p["kind"] == "serve_e2e" for p in rows) == 2

        # one full batch = exactly one transfer/dispatch/readback
        base = dict(engine.counters)
        futs = [engine.submit(img) for img in (land_a, land_b)]
        got = [f.result(timeout=300) for f in futs]
        # a future resolves before the dispatcher books its batch: wait
        # for the quiescent point before reading the counters
        assert engine.drain(timeout=30)
        engine.resume()
        delta = {k: engine.counters[k] - base[k]
                 for k in ("h2d_transfers", "dispatches", "readbacks",
                           "batches")}
        assert delta == {"h2d_transfers": 1, "dispatches": 1,
                         "readbacks": 1, "batches": 1}
        # the (B, cap, 6) readback is far below the legacy scores+deltas
        e2e_readback = engine.counters["readback_bytes"] - \
            base["readback_bytes"]
        assert 0 < e2e_readback < legacy_readback_per_batch
        got.append(engine.submit(port).result(timeout=300))

        # fused vs unfused detection-record parity at float tolerance
        for dets, ref in zip(got, expect):
            assert len(dets) == len(ref)
            for d, e in zip(dets, ref):
                assert d["cls"] == e["cls"]
                assert abs(d["score"] - e["score"]) < 0.02
                assert np.allclose(d["bbox"], e["bbox"], atol=1.0)

        # hot-reload param swap: zero recompiles under the fused kind,
        # identical detections (same weights back in)
        before = engine.counters["recompiles"]
        pred.update_params(params)
        again = engine.submit(land_a).result(timeout=300)
        assert engine.counters["recompiles"] == before == \
            engine.counters["warmup_programs"]
        assert len(again) == len(got[0])
        for d, e in zip(again, got[0]):
            assert d["cls"] == e["cls"]
            assert abs(d["score"] - e["score"]) < 1e-5
    finally:
        engine.stop()
