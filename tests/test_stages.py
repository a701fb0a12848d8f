"""The serving process accounts for its own time (CPU, tiny sizes).

``telemetry.stage`` — one clock, one ``Hist``, one profiler annotation a
stage; the engine's ``/metrics["stages"]`` / ``["setup"]`` / ``["t_s"]``;
the post-process counters against a recount; the stage names on a profiler
trace read back with ``benchmark.xplane``; and the ``jax.monitoring``
counters behind ``/metrics["compile"]["counters"]``.
"""

import subprocess
import sys
import threading

import numpy as np
import pytest

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.data import prepare_image
from mx_rcnn_tpu.serve import (ServeEngine, ServeOptions,
                               encode_image_payload, make_server,
                               unix_http_request, warmup)
from mx_rcnn_tpu.telemetry import Hist, tracectx
from mx_rcnn_tpu.telemetry.tracectx import NULL_TRACER
from tests.test_serve import make_engine, raw_image, tiny_cfg

# what a dispatcher turn observes once a batch (legacy path) ...
PER_BATCH = ("serve/assemble", "serve/forward", "serve/readback",
             "serve/postprocess", "serve/post/decode", "serve/post/nms",
             "serve/service_time")
# ... what a request observes once, and which of those only over HTTP
PER_REQUEST = ("serve/host_prep", "serve/stage_row", "serve/queue_wait",
               "serve/request_time")
PER_HTTP_REQUEST = ("frontend/read", "frontend/decode", "frontend/reply")
# the names that have to be events on the profiler's timeline: the
# dispatcher thread's, and a request thread's (serve/post/records is an
# event only: no clock of its is kept)
DISPATCHER_EVENTS = ("serve/idle", "serve/assemble", "serve/forward",
                     "serve/readback", "serve/post/decode", "serve/post/nms",
                     "serve/post/records")
REQUEST_EVENTS = ("frontend/read", "frontend/decode", "serve/host_prep",
                  "serve/stage_row", "frontend/reply")


# -- the helper --------------------------------------------------------------


def test_stage_observes_its_hist_and_sums_its_uses():
    h = Hist()
    st = telemetry.stage("unit/stage", h)
    with st:
        pass
    first = st.seconds
    assert h.count == 1 and h.sum == pytest.approx(first) and first > 0
    with st:  # entered again: the Hist sees each use, seconds their total
        pass
    assert h.count == 2 and st.seconds == pytest.approx(h.sum)
    assert st.seconds > first
    with telemetry.stage("unit/bare") as bare:  # no Hist: the clock alone
        pass
    assert bare.seconds > 0


def test_stage_feeds_the_sink_only_when_the_sink_is_on(tmp_path):
    assert not telemetry.get().enabled
    with telemetry.stage("unit/off", Hist()):
        pass
    assert telemetry.get().summary() == {}
    tel = telemetry.configure(str(tmp_path), stream=False, trace=True)
    try:
        h = Hist()
        for _ in range(3):
            with telemetry.stage("unit/on", h):
                pass
        summed = telemetry.stage("unit/summed")  # no Hist: nothing booked
        for _ in range(4):
            with summed:
                pass
        summ = tel.summary()
        # what tel.span did: a span a use, and no histogram of the sink's
        assert summ["spans"]["unit/on"]["count"] == 3 == h.count
        assert "unit/on" not in summ["hists"]
        assert "unit/off" not in summ["spans"]
        assert "unit/summed" not in summ["spans"]
        # ... until its owner books the uses, as one observation
        summed.book(h)
        assert h.count == 4 and summed.uses == 4
        assert tel.summary()["spans"]["unit/summed"]["count"] == 4
        recs = [r for r in tel._ring if r["kind"] == "span"]
        assert [r["name"] for r in recs] == ["unit/on"] * 3 + ["unit/summed"]
        # trace mode: a use carries its wall-clock start, like tel.span's
        assert all(r["t"] - 5 < r["ts"] <= r["t"] for r in recs[:3])
        assert recs[3]["n"] == 4 and "ts" not in recs[3]
        assert recs[3]["dur_s"] == pytest.approx(summed.seconds)
    finally:
        telemetry.shutdown()


def test_stage_works_in_a_process_that_never_imports_jax():
    code = ("import sys\n"
            "from mx_rcnn_tpu import telemetry\n"
            "h = telemetry.Hist()\n"
            "with telemetry.stage('unit/nojax', h) as st:\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'stage imported jax'\n"
            "assert h.count == 1 and st.seconds > 0\n"
            "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_null_tracer_still_raises_with_the_helper_in_place():
    """Tracing off: the engine times its stages through the helper and the
    (raising) NULL tracer is never reached — and still raises if it were."""
    assert tracectx.get() is NULL_TRACER
    engine = make_engine(tiny_cfg(), batch_size=2,
                         max_delay_ms=20000.0).start()  # full batches only
    try:
        futs = [engine.submit(raw_image(60, 100, v)) for v in (40, 90)]
        assert all(f.result(timeout=30) is not None for f in futs)
        assert engine.drain(timeout=30)
        m = engine.metrics()
        assert "trace" not in m
        assert m["stages"]["serve/forward"]["count"] == 1
    finally:
        engine.stop()
    for call in (lambda: NULL_TRACER.mint(),
                 lambda: NULL_TRACER.span(None, "engine/forward"),
                 lambda: NULL_TRACER.record(None, "engine/forward", 0.0)):
        with pytest.raises(RuntimeError, match="disabled"):
            call()


# -- the engine's clocks on /metrics (fake predictor: no compile) -----------


@pytest.mark.parametrize("arrivals", ["one at a time", "queued ahead"])
def test_metrics_stages_count_batches_and_requests_and_are_monotone(arrivals):
    """A turn is a claimed batch's launch plus the finish of the flight the
    turn before left (PR 33): whether the batches come one after another
    (each turn launches and finishes its own) or wait in the queue (each
    turn but the first launches one and finishes another), every stage and
    ``serve/service_time`` observe once a batch, and the stages of both
    halves lie inside the turns."""
    # a long delay: only full batches flush, however slowly a submit runs
    engine = make_engine(tiny_cfg(), batch_size=2,
                         max_delay_ms=20000.0).start()
    if arrivals == "queued ahead":
        # a launch that takes long enough for the next batches to queue:
        # the loop then finds one due at the top of the following turns
        engine.predictor.delay_s = 0.1
    try:
        snaps = []
        for n_batches in (3, 2):
            futs = []
            for i in range(n_batches):
                futs += [engine.submit(raw_image(60, 100, 20 * i + v))
                         for v in (40, 90)]  # a full batch: flushes at once
                if arrivals == "one at a time":
                    for f in futs:
                        f.result(timeout=30)
            for f in futs:
                f.result(timeout=30)
            assert engine.drain(timeout=30)  # the last batch is booked
            engine.resume()
            snaps.append(engine.metrics())
        first, second = snaps
        for m, batches in ((first, 3), (second, 5)):
            assert m["counters"]["batches"] == batches
            assert m["counters"]["overlapped_turns"] <= batches - 1
            assert (m["counters"]["overlapped_turns"] == 0) == (
                arrivals == "one at a time")
            for name in PER_BATCH:
                assert m["stages"][name]["count"] == batches, name
            for name in PER_REQUEST:
                assert m["stages"][name]["count"] == 2 * batches, name
            for name in PER_HTTP_REQUEST:  # nothing came over HTTP
                assert m["stages"][name] == {"count": 0, "sum_s": 0.0,
                                             "cpu_s": 0.0, "minflt": 0}
            s = {k: v["sum_s"] for k, v in m["stages"].items()}
            # flat siblings inside one turn: they cannot outlast it
            assert (s["serve/assemble"] + s["serve/forward"]
                    + s["serve/readback"] + s["serve/postprocess"]
                    <= s["serve/service_time"])
            assert (s["serve/post/decode"] + s["serve/post/nms"]
                    <= s["serve/postprocess"])
            assert m["stages"]["serve/idle"]["count"] >= 1
        assert set(first["stages"]) == set(second["stages"])
        for name, doc in first["stages"].items():
            assert second["stages"][name]["count"] >= doc["count"], name
            assert second["stages"][name]["sum_s"] >= doc["sum_s"], name
        assert second["t_s"] > first["t_s"]
        # the old keys are all still there
        assert {"counters", "queue_depth", "latency", "policy",
                "options"} <= set(second)
        assert "service_time_p50_ms" in second["latency"]
    finally:
        engine.stop()


def test_setup_split_reaches_metrics():
    engine = make_engine(tiny_cfg(), batch_size=2,
                         max_delay_ms=20000.0).start()  # full batches only
    try:
        assert engine.metrics()["setup"] == {}
        warmup(engine)
        assert engine.metrics()["setup"]["warmup_s"] > 0
        # serve.py's _build_engine writes the other three the same way
        engine.setup.update(model_s=0.5, params_s=1.5, predictor_s=0.25)
        assert engine.metrics()["setup"] == {
            "model_s": 0.5, "params_s": 1.5, "predictor_s": 0.25,
            "warmup_s": engine.setup["warmup_s"]}
        # warm-up left nothing unbooked behind it
        m = engine.metrics()
        assert m["counters"]["batches"] == 2
        assert m["stages"]["serve/service_time"]["count"] == 2
    finally:
        engine.stop()


# -- the real thing at a tiny size: HTTP, counters, the timeline -------------


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A warmed engine over the tiny real model behind a Unix-socket HTTP
    server -> (engine, predictor, cfg, post(img) -> detections)."""
    import jax

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
    sock = str(tmp_path_factory.mktemp("stages") / "serve.sock")
    server = make_server(engine, unix_socket=sock)
    th = threading.Thread(target=server.serve_forever, daemon=True)
    warmup(engine)
    th.start()

    def post(img):
        status, resp = unix_http_request(
            sock, "POST", "/predict", encode_image_payload(img), timeout=300)
        assert status == 200, resp
        return resp["detections"]

    try:
        yield engine, pred, cfg, post
    finally:
        server.shutdown()
        server.server_close()
        engine.stop()


def _quiet_metrics(engine):
    assert engine.drain(timeout=60)
    engine.resume()
    return engine.metrics()


def test_post_counters_match_a_recount_and_http_stages_count_requests(served):
    import jax

    engine, pred, cfg, post = served
    before = _quiet_metrics(engine)
    rng = np.random.RandomState(3)
    images = [rng.randint(0, 255, (h, w, 3), dtype=np.uint8)
              for h, w in ((60, 100), (100, 60), (48, 90))]
    dets = [post(img) for img in images]
    after = _quiet_metrics(engine)

    def delta(key):
        return after["counters"][key] - before["counters"][key]

    assert delta("served") == 3 and delta("batches") == 3  # one at a time
    # kept: the records that went back; candidates: the offline scores over
    # the threshold, row for row what per_class_nms selects from
    assert delta("post_kept") == sum(len(d) for d in dets) > 0
    candidates = 0
    for img in images:
        prepared, im_info = prepare_image(img, cfg, cfg.tpu.SCALES[0])
        _, valid, scores, _, _ = [
            np.asarray(jax.device_get(x)) for x in pred.predict(
                np.stack([prepared, prepared]), np.stack([im_info, im_info]))]
        candidates += int(((scores[0][:, 1:] > cfg.TEST.THRESH)
                           & valid[0][:, None].astype(bool)).sum())
    assert delta("post_candidates") == candidates >= delta("post_kept")
    # h2d beside readback: a padded batch of two inputs and their im_info
    prepared, im_info = prepare_image(images[0], cfg, cfg.tpu.SCALES[0])
    assert delta("h2d_bytes") == 3 * 2 * (prepared.nbytes
                                          + np.asarray(im_info).nbytes)
    assert delta("readback_bytes") > 0
    for name in PER_HTTP_REQUEST + ("serve/host_prep",):
        got = (after["stages"][name]["count"]
               - before["stages"][name]["count"])
        assert got == 3, name
        assert after["stages"][name]["sum_s"] > before["stages"][name]["sum_s"]
    for name in PER_BATCH:
        assert (after["stages"][name]["count"]
                - before["stages"][name]["count"]) == 3, name
    assert delta("recompiles") == 0
    assert after["setup"]["warmup_s"] > 0


def test_every_stage_is_a_flat_event_on_the_profilers_timeline(served,
                                                               tmp_path):
    """A profile around two batches, read back the way the benchmark reads
    its own: every stage of the table is a host event, and on one thread
    none of them encloses or overlaps another (an enclosing span would win
    every idle gap in ``xplane.host_label`` and name none).  The requests
    come one after another, so the request threads' events line up too."""
    import jax

    from benchmark import xplane

    engine, _, _, post = served
    _quiet_metrics(engine)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        post(raw_image(60, 100, 70))
        post(raw_image(100, 60, 70))
        _quiet_metrics(engine)
    finally:
        jax.profiler.stop_trace()
    planes = xplane.load(xplane.find_trace(str(tmp_path)))
    events = [e for pname, plane in planes.items()
              if pname.startswith("/host:")
              for line in plane.values() for e in line]
    seen = {name for name, _, _ in events}
    wanted = set(DISPATCHER_EVENTS + REQUEST_EVENTS)
    assert wanted <= seen, wanted - seen
    # the whole-loop clock is a Hist only, and nothing wraps a turn
    assert "serve/postprocess" not in seen and "serve/turn" not in seen
    for thread in (DISPATCHER_EVENTS, REQUEST_EVENTS):
        ours = sorted((s, s + d, n) for n, s, d in events if n in thread)
        for (s0, e0, n0), (s1, e1, n1) in zip(ours, ours[1:]):
            assert s1 >= e0, f"{n0} [{s0}, {e0}] overlaps {n1} [{s1}, {e1}]"
    # per image on the timeline: two requests, one image a batch
    assert sum(n == "serve/post/nms" for n, _, _ in events) == 2
    # and a gap the device spent waiting for the post-process is named.
    # (The profiler names the Python threads' line after the command: the
    # benchmark runs as ``python3``; ``host_label`` leaves out a line called
    # ``python``, which is what this suite's interpreter is called.)
    as_python3 = {pname: {("python3" if lname == "python" else lname): line
                          for lname, line in plane.items()}
                  for pname, plane in planes.items()}
    start, dur = next((s, d) for n, s, d in events if n == "serve/post/nms")
    assert xplane.host_label(as_python3, start,
                             start + dur) == "serve/post/nms"


# -- what XLA really did -----------------------------------------------------


def test_xla_counters_count_a_new_shape_once():
    import jax
    import jax.numpy as jnp

    from mx_rcnn_tpu.compile.registry import ProgramRegistry, xla_counters

    reg = ProgramRegistry(None)  # any registry starts the listeners

    @jax.jit
    def f(x):
        return (x * 3 + 1).sum()

    a = xla_counters()
    f(jnp.ones((7, 3))).block_until_ready()
    b = xla_counters()
    assert b["xla_compiles"] - a["xla_compiles"] >= 1
    assert b["jaxpr_traces"] - a["jaxpr_traces"] >= 1
    assert b["xla_compile_s"] > a["xla_compile_s"]
    f(jnp.ones((7, 3))).block_until_ready()  # the same shape: nothing new
    c = xla_counters()
    assert c == b
    f(jnp.ones((5, 3))).block_until_ready()  # another shape: once more
    d = xla_counters()
    assert d["xla_compiles"] - c["xla_compiles"] >= 1
    # the registry's snapshot (``/metrics["compile"]``) carries them beside
    # the markers' forecast
    counters = reg.snapshot()["counters"]
    assert {"aot_hit", "aot_miss", "xla_compiles", "xla_compile_s",
            "jaxpr_traces", "persistent_cache_hits",
            "persistent_cache_misses"} <= set(counters)
    assert counters["xla_compiles"] >= d["xla_compiles"]
