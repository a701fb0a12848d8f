"""SLO layer tier-1 tests (CPU, no network): the histogram primitive,
the engine's latency/policy surface, and the SLO controller's control law
(driven deterministically through the injectable-``now`` ``tick``).

The controller tests run against a real ``ServeEngine`` over the
``FakePredictor`` from ``test_serve`` — no model, no compile — and feed
the engine's own histograms directly, which is exactly the interface the
controller consumes in production.
"""

import json

import numpy as np
import pytest

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.serve import ControllerOptions, RejectedError, SLOController
from mx_rcnn_tpu.telemetry import HIST_LE, Hist, quantile_from_counts
from mx_rcnn_tpu.telemetry.obs import engine_summary, prometheus_text
from mx_rcnn_tpu.telemetry.report import aggregate, load_events

from tests.test_serve import make_engine, raw_image, tiny_cfg


@pytest.fixture(autouse=True)
def _restore_sink():
    yield
    telemetry.shutdown()


# -- histogram primitive ---------------------------------------------------


def test_hist_bucket_boundaries():
    h = Hist()
    # a value exactly ON a boundary lands in that boundary's bucket
    # (le is an UPPER bound, Prometheus semantics), one just above it in
    # the next; the tiniest and hugest values hit the edge buckets
    h.observe(HIST_LE[0])          # == first upper bound
    h.observe(HIST_LE[5])
    h.observe(HIST_LE[5] * 1.0001)
    h.observe(1e-9)                # far below the first bound
    h.observe(1e9)                 # beyond the last bound: overflow
    assert h.buckets[0] == 2       # 1e-9 and the exact first bound
    assert h.buckets[5] == 1
    assert h.buckets[6] == 1
    assert h.buckets[-1] == 1      # the +Inf overflow bucket
    assert h.count == 5 and len(h.buckets) == len(HIST_LE) + 1
    # quantile interpolation stays inside the containing bucket
    mid = Hist()
    for _ in range(100):
        mid.observe(0.010)
    lo = HIST_LE[max(i for i, le in enumerate(HIST_LE) if le < 0.010)]
    hi = min(le for le in HIST_LE if le >= 0.010)
    assert lo < mid.quantile(0.5) <= hi
    # empty histogram has no quantile
    assert Hist().quantile(0.5) is None
    assert quantile_from_counts(HIST_LE, [0] * (len(HIST_LE) + 1), 0,
                                0.99) is None


def test_hist_merge_associative_across_ranks():
    rng = np.random.RandomState(0)
    parts = []
    for _ in range(3):  # three "ranks" with different distributions
        h = Hist()
        for v in rng.lognormal(-4, 1, 200):
            h.observe(float(v))
        parts.append(h)
    ab_c = Hist().merge(parts[0]).merge(parts[1]).merge(parts[2])
    c_ba = Hist().merge(parts[2]).merge(parts[1]).merge(parts[0])
    assert ab_c.buckets == c_ba.buckets
    assert ab_c.count == c_ba.count == 600
    assert abs(ab_c.sum - c_ba.sum) < 1e-9
    assert ab_c.quantile(0.99) == c_ba.quantile(0.99)
    # dict form merges identically (the snapshot-fold path)
    via_dict = Hist().merge(parts[0].to_dict()).merge(
        parts[1].to_dict()).merge(parts[2].to_dict())
    assert via_dict.buckets == ab_c.buckets
    # boundary-version mismatch is an error, not silent corruption
    bad = parts[0].to_dict()
    bad["le"] = bad["le"][:-1]
    with pytest.raises(ValueError):
        Hist().merge(bad)


def test_hist_window_quantile_sees_only_recent():
    h = Hist()
    for i in range(100):               # old regime: 1 ms
        h.observe(0.001, now=float(i))
    for i in range(100, 120):          # recent regime: 1 s
        h.observe(1.0, now=float(i))
    assert h.quantile(0.5) < 0.01      # lifetime: dominated by the old
    recent = h.window_quantile(0.5, 15.0, now=119.0)
    assert recent > 0.5                # window: the new regime only
    # a window longer than the run falls back to the whole history
    assert h.window_quantile(0.5, 1e6, now=119.0) == h.quantile(0.5)


def test_hist_prometheus_exposition_roundtrip():
    h = Hist()
    vals = [0.0005, 0.002, 0.002, 0.05, 2.0]
    for v in vals:
        h.observe(v)
    txt = prometheus_text({0: {"hists": {"serve/request_time": h.to_dict()},
                               "counters": {}, "gauges": {}, "spans": {}}})
    assert "# TYPE mxr_serve_request_time_seconds histogram" in txt
    # parse the family back: cumulative buckets, +Inf == _count, _sum
    buckets = {}
    total = None
    ssum = None
    for line in txt.splitlines():
        if line.startswith("mxr_serve_request_time_seconds_bucket"):
            le = line.split('le="')[1].split('"')[0]
            buckets[le] = int(line.rsplit(" ", 1)[1])
        elif line.startswith("mxr_serve_request_time_seconds_count"):
            total = int(line.rsplit(" ", 1)[1])
        elif line.startswith("mxr_serve_request_time_seconds_sum"):
            ssum = float(line.rsplit(" ", 1)[1])
    assert total == len(vals) and buckets["+Inf"] == total
    assert abs(ssum - sum(vals)) < 1e-9
    # cumulative counts are monotone and recover the per-bucket counts
    finite = [buckets[k] for k in buckets if k != "+Inf"]
    assert finite == sorted(finite)
    per_bucket = np.diff([0] + finite).tolist()
    assert per_bucket == h.buckets[:len(per_bucket)]


def test_sink_observe_jsonl_and_report_fold(tmp_path):
    tel = telemetry.configure(str(tmp_path), run_meta={"driver": "t"})
    for v in (0.001, 0.004, 0.2):
        tel.observe("serve/request_time", v)
    assert tel.hist_quantile("serve/request_time", 0.5) is not None
    assert tel.hist_quantile("nope", 0.5) is None
    summ = tel.summary()
    assert summ["hists"]["serve/request_time"]["count"] == 3
    telemetry.shutdown()
    events = load_events([str(tmp_path)])
    kinds = {e["kind"] for e in events}
    assert "hist" in kinds
    folded = aggregate(events)
    # the offline fold reproduces the live sink's distribution exactly
    assert folded["hists"]["serve/request_time"] == \
        summ["hists"]["serve/request_time"]


# -- engine latency/policy surface -----------------------------------------


def test_engine_records_latency_hists_and_metrics():
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=2, max_delay_ms=1.0).start()
    try:
        futs = [engine.submit(raw_image(60, 100, 50)) for _ in range(4)]
        for f in futs:
            f.result(timeout=30)
    finally:
        engine.stop()
    assert engine.hists["serve/request_time"].count == 4
    assert engine.hists["serve/queue_wait"].count == 4
    assert engine.hists["serve/service_time"].count >= 1
    hists = engine.latency_hists()
    per_bucket = [k for k in hists if k.startswith("serve/request_time/")]
    assert per_bucket and hists[per_bucket[0]].count == 4
    m = engine.metrics()
    assert m["latency"]["request_time_p99_ms"] > 0
    assert m["latency"]["request_time_p50_ms"] <= \
        m["latency"]["request_time_p99_ms"]
    # the frontend's Prometheus registry carries the histogram family
    # with nonzero _count plus the engine counters
    summ = engine_summary(engine)
    assert summ["hists"]["serve/request_time"]["count"] == 4
    txt = prometheus_text({0: summ})
    assert "mxr_serve_request_time_seconds_bucket" in txt
    assert 'mxr_serve_request_time_seconds_count{rank="0"} 4' in txt


def test_bucket_policy_clamps_and_flush_threshold():
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=4, max_delay_ms=300.0)
    fake = engine.predictor
    key = engine.bucket_key(60, 100)
    engine.set_bucket_policy(key, max_batch=99, max_delay_ms=-5)
    assert engine.bucket_policy(key) == (4, 0.0)  # clamped both ways
    engine.set_bucket_policy(key, max_batch=2, max_delay_ms=300.0)
    assert engine.bucket_policy(key) == (2, 300.0)
    # two requests now make a "full" flush despite batch_size=4 — and the
    # forward is still padded to the compiled batch of 4
    futs = [engine.submit(raw_image(60, 100, v)) for v in (40, 200)]
    engine.start()
    try:
        for f in futs:
            f.result(timeout=30)
    finally:
        engine.stop()
    assert len(fake.batches) == 1 and fake.batches[0][0] == 4
    assert engine.counters["served"] == 2


def test_admit_limit_sheds_distinct_from_queue_full():
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=2, max_queue=8)
    engine.set_admit_limit(2)
    for _ in range(2):  # not started: nothing drains
        engine.submit(raw_image(60, 100, 50))
    with pytest.raises(RejectedError, match="load shed"):
        engine.submit(raw_image(60, 100, 50))
    assert engine.counters["shed"] == 1
    assert engine.counters["rejected"] == 0  # shed is its own counter
    engine.set_admit_limit(None)
    engine.submit(raw_image(60, 100, 50))    # back to max_queue rules
    assert engine.counters["requests"] == 3
    engine.stop()


# -- the SLO controller ----------------------------------------------------


def _controller(engine, **kw):
    kw.setdefault("target_p99_ms", 100.0)
    kw.setdefault("min_samples", 4)
    kw.setdefault("relax_after", 1)
    return SLOController(engine, ControllerOptions(**kw))


def test_controller_tightens_then_relaxes():
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=4, max_delay_ms=20.0)
    key = engine.bucket_key(60, 100)
    engine.submit(raw_image(60, 100, 50))  # make the bucket known
    ctrl = _controller(engine, window_s=10.0)
    ctrl.engine.controller = ctrl  # what start() does, sans thread
    # breach: p99 far over target inside the window
    for i in range(10):
        engine.hists["serve/request_time"].observe(0.5, now=float(i))
    acted = ctrl.tick(now=10.0)
    assert any(a[0] == "tighten" for a in acted)
    b1, d1 = engine.bucket_policy(key)
    assert b1 == 3 and d1 == 10.0  # -1 batch, delay halved
    ctrl.tick(now=10.5)
    assert engine.bucket_policy(key)[0] == 2
    # repeated breaches converge to the floor, then stop acting
    for t in range(11, 30):
        engine.hists["serve/request_time"].observe(0.5, now=float(t))
        ctrl.tick(now=float(t))
    assert engine.bucket_policy(key) == (1, 0.0)
    assert ctrl.tick(now=30.0) == []  # at the floor: no decision spam
    # recovery: fast traffic far past the old window → healthy → relax
    # back toward the configured (4, 20.0)
    for t in range(100, 110):
        engine.hists["serve/request_time"].observe(0.001, now=float(t))
    for t in range(110, 140):
        ctrl.tick(now=float(t))
    assert engine.bucket_policy(key) == (4, 20.0)
    assert ctrl.decisions > 0 and ctrl.ticks > 0
    engine.stop()


def test_controller_sheds_on_queue_trend_and_recovers(tmp_path):
    telemetry.configure(str(tmp_path), run_meta={"driver": "t"})
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=2, max_queue=16)
    ctrl = _controller(engine, window_s=5.0)
    ctrl.engine.controller = ctrl
    # queue grows tick over tick with nothing draining (engine unstarted):
    # slope > 0, drain time infinite → predictive shed; once the cap is
    # on, the rest of the ramp is refused at submit
    shed_err = None
    for t in range(4):
        for _ in range(3):
            try:
                engine.submit(raw_image(60, 100, 50))
            except RejectedError as e:
                shed_err = e
        ctrl.tick(now=float(t))
    assert ctrl.state()["shedding"] is True
    assert engine.metrics()["admit_limit"] == 2  # max(batch_size, 0)
    assert shed_err is not None and "load shed" in str(shed_err)
    assert engine.counters["shed"] >= 1
    # the shed-on transition left a flight dump and slo/ telemetry
    assert (tmp_path / "flight_0.jsonl").exists()
    flight = [json.loads(ln) for ln in
              (tmp_path / "flight_0.jsonl").read_text().splitlines()]
    assert any(e["kind"] == "meta" and e["name"] == "flight_trigger"
               and e["fields"]["reason"] == "slo_shed" for e in flight)
    summ = telemetry.get().summary()
    assert summ["counters"]["slo/shed_on"] == 1
    assert summ["counters"]["slo/decisions"] >= 1
    # drain the queue; with a falling trend the controller lifts the cap
    with engine._lock:
        for q in engine._queues.values():
            q.clear()
    for t in range(100, 104):
        ctrl.tick(now=float(t))
    assert ctrl.state()["shedding"] is False
    assert engine.metrics()["admit_limit"] is None
    assert telemetry.get().summary()["counters"]["slo/shed_off"] == 1
    engine.submit(raw_image(60, 100, 50))  # admissions open again
    engine.stop()


def test_controller_decisions_are_telemetry_events(tmp_path):
    telemetry.configure(str(tmp_path), run_meta={"driver": "t"})
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=4, max_delay_ms=20.0)
    engine.submit(raw_image(60, 100, 50))
    ctrl = _controller(engine)
    ctrl.engine.controller = ctrl
    for i in range(10):
        engine.hists["serve/request_time"].observe(0.5, now=float(i))
    ctrl.tick(now=10.0)
    telemetry.shutdown()
    events = load_events([str(tmp_path)])
    decisions = [e for e in events
                 if e["kind"] == "meta" and e["name"] == "slo_decision"]
    assert decisions and decisions[0]["fields"]["action"] == "tighten"
    assert decisions[0]["fields"]["bucket"]  # names the adapted bucket
    folded = aggregate(events)
    assert folded["counters"]["slo/tighten"] >= 1
    assert "slo/p99_ms" in folded["gauges"]
    # live controller state rides the /metrics payloads
    m = engine.metrics()
    assert m["controller"]["ticks"] == 1
    assert m["controller"]["target_p99_ms"] == 100.0
    assert m["policy"]  # effective per-bucket policy is visible
    summ = engine_summary(engine)
    assert "slo/target_p99_ms" in summ["gauges"]
    assert any(k.startswith("slo/bucket_") for k in summ["gauges"])
    engine.stop()


def test_controller_start_stop_restores_policy():
    cfg = tiny_cfg()
    engine = make_engine(cfg, batch_size=4, max_delay_ms=20.0)
    key = engine.bucket_key(60, 100)
    engine.submit(raw_image(60, 100, 50))
    ctrl = _controller(engine, interval_s=30.0).start()  # no tick fires
    assert engine.controller is ctrl
    engine.set_bucket_policy(key, max_batch=1, max_delay_ms=0.0)
    engine.set_admit_limit(2)
    ctrl.stop()
    assert engine.controller is None
    assert engine.bucket_policy(key) == (4, 20.0)
    with engine._lock:
        assert engine._admit_limit is None
    engine.stop()

