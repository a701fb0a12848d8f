"""Streaming serving contract tests (CPU).

The tentpole guarantees, each pinned here: with the skip gate OFF a
stream is byte-for-byte the ``/predict`` path; a skip answers from the
reference frame's cache with ZERO engine counter/hist deltas (the SLO
controller never sees it); scene cuts, bucket changes, and the
``max_skip`` budget always force the full path; per-stream response
order survives cross-stream batch coalescing; ``frame_delta`` programs
are ordinary registry citizens (kind-labeled, first-seen accounting,
no engine ``recompiles`` pollution); and the ``/stream`` NDJSON + stdio
transports speak ``/predict``'s status vocabulary (400/409/503/504).
Runs against the shape-faithful FakePredictor — the gate's jit is the
only compiled program, tiny on CPU.
"""

import io
import json

import numpy as np

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.compile.registry import ProgramRegistry
from mx_rcnn_tpu.data import prepare_image
from mx_rcnn_tpu.serve import (StaleSeqError, StreamManager, StreamOptions,
                               encode_image_payload, make_server,
                               run_stream_stdio, unix_http_request)
from mx_rcnn_tpu.serve.frontend import unix_http_request_raw
from tests.test_serve import FakePredictor, make_engine, raw_image, tiny_cfg


def _mgr(engine, **opts):
    return StreamManager(engine, StreamOptions(**opts))


# -- gate off: pure coalescing, byte-identical results ----------------------


def test_gate_off_stream_byte_identical_to_predict():
    cfg = tiny_cfg()
    rng = np.random.RandomState(3)
    frames = [rng.randint(0, 255, (60, 100, 3), dtype=np.uint8)
              for _ in range(4)]

    plain = make_engine(cfg).start()
    try:
        expect = [plain.submit(f).result(timeout=60) for f in frames]
    finally:
        plain.stop()

    engine = make_engine(cfg).start()
    mgr = _mgr(engine)  # skip_thresh 0 → gate off
    try:
        assert not mgr.gate_enabled
        assert mgr.warmup() == 0  # no gate → no programs
        results = [mgr.submit_frame("cam", i + 1, f)
                   for i, f in enumerate(frames)]
        got = [r.result(timeout=60) for r in results]
    finally:
        engine.stop()

    # byte-identical, not merely close: the serialized responses agree
    assert (json.dumps(got, sort_keys=True)
            == json.dumps(expect, sort_keys=True))
    assert all(r.skipped is False and r.delta is None for r in results)
    assert mgr.counters["forwarded"] == len(frames)
    assert mgr.counters["skipped"] == 0
    assert mgr.metrics()["skip_fraction"] == 0.0


def test_stale_or_duplicate_seq_rejected():
    cfg = tiny_cfg()
    engine = make_engine(cfg).start()
    mgr = _mgr(engine)
    try:
        mgr.submit_frame("cam", 5, raw_image(60, 100, 80)).result(timeout=60)
        for bad in (5, 3):  # duplicate, then regression
            try:
                mgr.submit_frame("cam", bad, raw_image(60, 100, 80))
                raise AssertionError("stale seq accepted")
            except StaleSeqError:
                pass
        # the high-water mark survives the rejections
        mgr.submit_frame("cam", 6, raw_image(60, 100, 80)).result(timeout=60)
    finally:
        engine.stop()
    assert mgr.counters["stale_seq"] == 2
    assert mgr.counters["frames"] == 2  # only accepted frames count


# -- the skip fast path -----------------------------------------------------


def test_skip_serves_cached_with_zero_engine_deltas():
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=2).start()
    mgr = _mgr(engine, skip_thresh=3.0, max_skip=8)
    try:
        base_img = raw_image(60, 100, 100)
        first = mgr.submit_frame("cam", 1, base_img)
        ref = first.result(timeout=60)
        assert first.skipped is False

        base = dict(engine.counters)
        svc = engine.hists["serve/service_time"].count
        req = engine.hists["serve/request_time"].count

        noisy = base_img.copy()
        noisy[::2, ::2, 0] += 1  # sensor noise: mean |delta| ≪ thresh
        res = mgr.submit_frame("cam", 2, noisy)
        assert res.skipped is True
        assert res.delta is not None and res.delta < 3.0
        assert res.queue_wait_s is None
        assert res.result(timeout=60) == ref  # the cached detections

        # the subsystem's core guarantee: a skip is invisible to the
        # engine — no request, no batch, no dispatch, no readback, and
        # no service_time/request_time observation for the SLO
        # controller to mistake for a fast forward
        assert {k: engine.counters[k] - base[k]
                for k in base if engine.counters[k] != base[k]} == {}
        assert engine.hists["serve/service_time"].count == svc
        assert engine.hists["serve/request_time"].count == req
    finally:
        engine.stop()
    assert mgr.counters["skipped"] == 1
    assert mgr.hists["stream/skip_time"].count == 1
    m = mgr.metrics()
    assert m["skip_fraction"] == 0.5
    assert m["counters"]["delta_dispatches"] >= 1


def test_scene_cut_always_takes_full_path():
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=2).start()
    mgr = _mgr(engine, skip_thresh=3.0)
    fake = engine.predictor
    try:
        a = raw_image(60, 100, 10)
        cut = raw_image(60, 100, 220)  # hard cut: huge mean delta
        r1 = mgr.submit_frame("cam", 1, a)
        d1 = r1.result(timeout=60)
        r2 = mgr.submit_frame("cam", 2, cut)
        d2 = r2.result(timeout=60)
        assert r2.skipped is False
        assert r2.delta is not None and r2.delta >= 3.0
        # the cut frame's OWN detections, not the reference's
        prepared, _ = prepare_image(cut, cfg, cfg.tpu.SCALES[0])
        assert abs(d2[0]["score"] - fake.row_score(prepared)) < 1e-5
        assert d2[0]["score"] != d1[0]["score"]
    finally:
        engine.stop()
    assert mgr.counters["forwarded"] == 2
    assert mgr.counters["skipped"] == 0


def test_max_skip_budget_and_bucket_switch_force_refresh():
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=2).start()
    mgr = _mgr(engine, skip_thresh=5.0, max_skip=2)
    try:
        land = raw_image(60, 100, 100)
        seqs = []
        for seq in (1, 2, 3, 4):
            seqs.append(mgr.submit_frame("cam", seq, land))
            seqs[-1].result(timeout=60)
        # 1 forwards, 2–3 skip, 4 exhausts the budget → forced refresh
        assert [r.skipped for r in seqs] == [False, True, True, False]
        assert seqs[3].delta is None  # refreshed before the gate ran
        assert mgr.counters["refreshes"] == 1

        # orientation flip: new bucket → full path, then skipping resumes
        port = raw_image(100, 60, 100)
        r5 = mgr.submit_frame("cam", 5, port)
        r5.result(timeout=60)
        r6 = mgr.submit_frame("cam", 6, port)
        r6.result(timeout=60)
        assert r5.skipped is False and r5.delta is None
        assert r6.skipped is True
        assert mgr.counters["bucket_switches"] == 1
    finally:
        engine.stop()


def test_hot_reload_generation_invalidates_reference():
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=2).start()
    mgr = _mgr(engine, skip_thresh=5.0)
    try:
        img = raw_image(60, 100, 100)
        mgr.submit_frame("cam", 1, img).result(timeout=60)
        engine.generation += 1  # what /admin/reload does on swap
        r2 = mgr.submit_frame("cam", 2, img)
        r2.result(timeout=60)
        # identical pixels, but stale-generation detections must not serve
        assert r2.skipped is False
    finally:
        engine.stop()


# -- cross-stream coalescing ------------------------------------------------


def test_cross_stream_coalescing_preserves_per_stream_order():
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=2, max_delay_ms=50.0)
    mgr = _mgr(engine)
    fake = engine.predictor
    values = {"a": (30, 90, 150), "b": (60, 120, 210)}
    results = {"a": [], "b": []}
    # interleave two streams' frames pre-start: each full same-bucket
    # batch must mix both streams
    for seq in range(3):
        for sid in ("a", "b"):
            img = raw_image(60, 100, values[sid][seq])
            results[sid].append(mgr.submit_frame(sid, seq + 1, img))
    engine.start()
    try:
        dets = {sid: [r.result(timeout=60) for r in rs]
                for sid, rs in results.items()}
    finally:
        engine.stop()

    # every batch was full and cross-stream
    assert all(b[0] == 2 for b in fake.batches)
    assert engine.counters["stream_batches"] == 3
    assert engine.counters["stream_batch_frames"] == 6
    assert engine.counters["stream_coalesced_batches"] == 3

    # per-stream order: response i carries frame i's OWN score
    for sid in ("a", "b"):
        for seq in range(3):
            img = raw_image(60, 100, values[sid][seq])
            prepared, _ = prepare_image(img, cfg, cfg.tpu.SCALES[0])
            assert abs(dets[sid][seq][0]["score"]
                       - fake.row_score(prepared)) < 1e-5

    m = mgr.metrics()
    assert m["counters"]["coalesced_batches"] == 3
    assert m["batch_occupancy"] == 1.0


# -- frame_delta as a registry citizen -------------------------------------


def test_frame_delta_is_a_registry_citizen():
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=2).start()
    reg = ProgramRegistry()  # standalone: FakePredictor carries none
    mgr = StreamManager(engine, StreamOptions(skip_thresh=3.0),
                        registry=reg)
    try:
        # warmup compiles one delta program per orientation bucket —
        # registry-level accounting only, NEVER the engine's
        # recompiles/warmup_programs (those count forward programs)
        assert mgr.warmup() == 2
        assert engine.counters["recompiles"] == 0
        assert engine.counters["warmup_programs"] == 0
        assert reg.counters["programs"] == 2
        rows = reg.snapshot()["programs"]
        assert len(rows) == 2
        assert all(p["kind"] == "frame_delta" for p in rows)

        # steady-state traffic reuses them — no growth, and the gate
        # dispatch adds nothing to the engine's compile accounting
        img = raw_image(60, 100, 100)
        mgr.submit_frame("cam", 1, img).result(timeout=60)
        rec = engine.counters["recompiles"]  # the forward's own shape
        assert mgr.submit_frame("cam", 2, img).skipped is True
        assert reg.counters["programs"] == 2
        assert engine.counters["recompiles"] == rec
        assert mgr.counters["delta_dispatches"] == 3  # 2 warmup + 1 gate
    finally:
        engine.stop()


# -- transports: /stream NDJSON + stdio -------------------------------------


def test_stream_http_ndjson_pipelined_statuses_and_metrics(tmp_path):
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=2).start()
    mgr = _mgr(engine, skip_thresh=3.0)
    sock = str(tmp_path / "stream.sock")
    server = make_server(engine, unix_socket=sock, stream=mgr)
    import threading
    th = threading.Thread(target=server.serve_forever, daemon=True)
    th.start()
    try:
        img = raw_image(60, 100, 100)
        frame = dict(encode_image_payload(img), stream_id="cam")
        lines = [
            json.dumps(dict(frame, seq=1)),        # forward
            json.dumps(dict(frame, seq=2)),        # identical → skip
            "not json {",                          # 400
            json.dumps(dict(frame, seq=2)),        # duplicate → 409
            json.dumps({"seq": 3, "image_b64": "x"}),  # no stream_id → 400
        ]
        status, raw, ctype = unix_http_request_raw(
            sock, "POST", "/stream", "\n".join(lines).encode())
        assert status == 200 and "ndjson" in ctype
        replies = [json.loads(ln) for ln in raw.decode().splitlines()]
        assert [r["status"] for r in replies] == [200, 200, 400, 409, 400]
        assert replies[0]["skipped"] is False
        assert replies[1]["skipped"] is True
        assert replies[1]["detections"] == replies[0]["detections"]
        assert replies[1]["delta"] < 3.0

        # /metrics grows the stream section, and the Prometheus view
        # renders without choking on it
        status, m = unix_http_request(sock, "GET", "/metrics")
        assert status == 200
        st = m["stream"]
        assert st["active_streams"] == 1
        assert st["counters"]["skipped"] == 1
        assert st["counters"]["frames"] == 2
        assert st["options"]["skip_thresh"] == 3.0
        status, prom = unix_http_request(sock, "GET", "/metrics?format=prom")
        assert status == 200 and "stream" in prom
    finally:
        server.shutdown()
        server.server_close()
        engine.stop()


def test_stream_http_404_when_streaming_disabled(tmp_path):
    cfg = tiny_cfg()
    engine = make_engine(cfg).start()
    sock = str(tmp_path / "plain.sock")
    server = make_server(engine, unix_socket=sock)  # no StreamManager
    import threading
    th = threading.Thread(target=server.serve_forever, daemon=True)
    th.start()
    try:
        status, resp = unix_http_request(
            sock, "POST", "/stream",
            dict(encode_image_payload(raw_image(60, 100, 9)),
                 stream_id="cam", seq=1))
        assert status == 404
        assert "--stream" in resp["error"]
    finally:
        server.shutdown()
        server.server_close()
        engine.stop()


def test_run_stream_stdio_round_trip():
    cfg = tiny_cfg()
    engine = make_engine(cfg).start()
    mgr = _mgr(engine)
    img = raw_image(60, 100, 70)
    frame = dict(encode_image_payload(img), stream_id="cam")
    inp = io.StringIO("\n".join([
        json.dumps(dict(frame, seq=1)),
        json.dumps(dict(frame, seq=1)),  # duplicate → 409
        json.dumps(dict(frame, seq=2)),
    ]) + "\n")
    out = io.StringIO()
    try:
        run_stream_stdio(mgr, inp=inp, out=out)
    finally:
        engine.stop()
    replies = [json.loads(ln) for ln in out.getvalue().splitlines()]
    assert [r["status"] for r in replies] == [200, 409, 200]
    assert replies[0]["detections"] == replies[2]["detections"]
    assert replies[0]["seq"] == 1 and replies[2]["seq"] == 2


# -- telemetry report section ---------------------------------------------


def test_telemetry_report_streaming_section(tmp_path):
    from mx_rcnn_tpu.telemetry import report as trep
    tel = telemetry.configure(str(tmp_path), run_meta={"driver": "t"})
    tel.counter("stream/frames", 8)
    tel.counter("stream/skipped", 5)
    tel.counter("serve/requests", 3)
    telemetry.shutdown()
    summary = trep.aggregate(trep.load_events([str(tmp_path)]))
    table = trep.render_table(summary)
    assert "streaming" in table
    block = table[table.index("streaming"):]
    assert "stream/skipped" in block
    assert "stream/coalesced_batches" in block  # zeros included
