"""Live observability plane tier-1 tests (CPU).

Covers the three tentpole pieces end to end without network flakiness:
Prometheus rendering + the rank-0 obs server folding peer snapshot files
(the cross-rank scrape contract, emulated with a second rank's sink
publishing through the same snapshot files a real peer process would),
the flight recorder through real ``fit`` runs (NaN halt and SIGTERM via
``tests/faults.py``), and the Chrome trace export (nesting + JSON round
trip).  Satellites ride along: gauge min/max/last exposure and the serve
frontend's content negotiation.
"""

import glob
import json
import os
import pathlib
import socket
import subprocess
import sys
import threading
import urllib.request

import pytest

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.telemetry import RING_SIZE, Telemetry
from mx_rcnn_tpu.telemetry.obs import (ObsPlane, ObsServer, prometheus_text,
                                       read_peer_snapshots, write_snapshot)
from mx_rcnn_tpu.telemetry.trace import chrome_trace
from mx_rcnn_tpu.train import NonFiniteLossError, ResilienceOptions, fit

from .faults import NanBatchLoader, SignalAtBatchLoader
from .test_resilience import tiny_data, tiny_model

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _restore_sink():
    """Every test leaves the module-global sink as it found it: NULL."""
    yield
    telemetry.shutdown()


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def http_get(port, path, timeout=10.0):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=timeout) as r:
        return r.status, r.read().decode()


# -- prometheus rendering --------------------------------------------------


def test_prometheus_text_rendering():
    per_rank = {
        0: {"counters": {"train/steps": 7, "train/recompile": 2},
            "spans": {"train/dispatch": {"count": 3, "total_s": 1.5,
                                         "mean_s": 0.5, "min_s": 0.25,
                                         "max_s": 0.75}},
            "gauges": {"loader/queue_depth": {"count": 4, "mean": 2.5,
                                              "min": 0.0, "max": 9.0,
                                              "last": 2.0}}},
        1: {"counters": {"train/steps": 5}},
    }
    text = prometheus_text(per_rank, ages={1: 1.5})
    assert text.endswith("\n")
    lines = text.splitlines()
    # counters, labeled per rank, family TYPE declared once
    assert 'mxr_train_steps_total{rank="0"} 7' in lines
    assert 'mxr_train_steps_total{rank="1"} 5' in lines
    assert lines.count("# TYPE mxr_train_steps_total counter") == 1
    # spans → seconds/calls counters + max gauge
    assert 'mxr_train_dispatch_seconds_total{rank="0"} 1.5' in lines
    assert 'mxr_train_dispatch_calls_total{rank="0"} 3' in lines
    assert 'mxr_train_dispatch_seconds_max{rank="0"} 0.75' in lines
    # gauges expose the extremes, not just the final sample
    assert 'mxr_loader_queue_depth{rank="0",stat="last"} 2.0' in lines
    assert 'mxr_loader_queue_depth{rank="0",stat="min"} 0.0' in lines
    assert 'mxr_loader_queue_depth{rank="0",stat="max"} 9.0' in lines
    assert 'mxr_loader_queue_depth{rank="0",stat="mean"} 2.5' in lines
    # liveness + snapshot staleness
    assert 'mxr_up{rank="0"} 1' in lines and 'mxr_up{rank="1"} 1' in lines
    assert 'mxr_snapshot_age_seconds{rank="1"} 1.5' in lines


def test_gauge_summary_extremes_feed_the_endpoint(tmp_path):
    # the /metrics gauge stats come straight from Telemetry.summary():
    # min/max/last must survive the sink → summary → render path
    tel = Telemetry(str(tmp_path), rank=0)
    for v in (3.0, 9.0, 1.0):
        tel.gauge("loader/queue_depth", v)
    text = prometheus_text({0: tel.summary()})
    tel.close()
    assert 'mxr_loader_queue_depth{rank="0",stat="min"} 1.0' in text
    assert 'mxr_loader_queue_depth{rank="0",stat="max"} 9.0' in text
    assert 'mxr_loader_queue_depth{rank="0",stat="last"} 1.0' in text


def _lint_exposition(text):
    """Prometheus exposition lint (ISSUE 20 satellite): every sampled
    ``mxr_*`` family must declare ``# HELP`` then ``# TYPE`` exactly
    once, both before the family's first sample."""
    helped, typed, sampled = set(), set(), set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            fam = line.split()[2]
            assert fam not in helped, f"duplicate HELP for {fam}"
            assert fam not in sampled, f"HELP after samples for {fam}"
            helped.add(fam)
        elif line.startswith("# TYPE "):
            fam = line.split()[2]
            assert fam not in typed, f"duplicate TYPE for {fam}"
            assert fam in helped, f"TYPE before HELP for {fam}"
            typed.add(fam)
        elif not line.startswith("#"):
            fam = line.split("{", 1)[0].split(" ", 1)[0]
            if fam not in typed:
                # histogram samples hang off the base family's TYPE
                base = fam.rsplit("_", 1)[0]
                assert (fam.endswith(("_bucket", "_sum", "_count"))
                        and base in typed), \
                    f"sample before TYPE for {fam}"
                fam = base
            sampled.add(fam)
    assert sampled, "exposition rendered no samples at all"


def test_exposition_lint_every_family_has_help_and_type(tmp_path):
    tel = Telemetry(str(tmp_path), rank=0)
    tel.counter("train/steps", 7)
    tel.counter("serve/requests", 3)
    tel.gauge("loader/queue_depth", 2.0)
    tel.observe("serve/request_time", 0.05)
    with tel.span("train/dispatch"):
        pass
    text = prometheus_text({0: tel.summary()}, ages={0: 0.5})
    tel.close()
    _lint_exposition(text)
    # the appended mxr_alert_state family (serve_prometheus /
    # fabric_prometheus with a watchtower attached) lints the same way
    from mx_rcnn_tpu.telemetry.watch import Watchtower, alert_state_lines

    wt = Watchtower(rules=[{"name": "hot", "kind": "threshold",
                            "metric": "m", "op": ">", "value": 1.0}],
                    summary_fn=lambda: {"gauges": {"m": {"last": 5.0}}})
    wt.tick(now=0.0)
    _lint_exposition(text + "\n".join(alert_state_lines(wt, now=0.0))
                     + "\n")


# -- obs server + cross-rank fold ------------------------------------------


def test_obs_server_scrape_folds_both_ranks(tmp_path):
    """The acceptance contract: one rank-0 scrape returns metrics labeled
    for every rank.  Rank 1 publishes through the same snapshot file a
    real peer process drops under --telemetry-dir."""
    d = str(tmp_path)
    peer = Telemetry(d, rank=1, world=2)
    peer.counter("train/steps", 5)
    peer.gauge("loader/queue_depth", 3.0)
    assert write_snapshot(peer) == os.path.join(d, "snapshot_rank1.json")
    peer.close()

    telemetry.configure(d, rank=0, world=2)
    telemetry.get().counter("train/steps", 7)
    srv = ObsServer(0, telemetry_dir=d)  # port 0 → ephemeral
    try:
        status, body = http_get(srv.port, "/metrics")
        assert status == 200
        assert 'mxr_train_steps_total{rank="0"} 7' in body
        assert 'mxr_train_steps_total{rank="1"} 5' in body
        assert 'mxr_snapshot_age_seconds{rank="1"}' in body
        status, health = http_get(srv.port, "/healthz")
        assert status == 200 and json.loads(health)["status"] == "ok"
        with pytest.raises(urllib.error.HTTPError) as ei:
            http_get(srv.port, "/nope")
        assert ei.value.code == 404
    finally:
        srv.close()


def test_obs_scrape_with_real_peer_process(tmp_path):
    """mp_worker.py-style: rank 1 is a REAL second OS process publishing
    its snapshot over the shared telemetry dir; the rank-0 scrape in this
    process sees both ranks.  The peer imports only the telemetry
    subpackage (no jax), so this costs one interpreter startup."""
    d = str(tmp_path)
    peer_prog = (
        "import sys\n"
        "from mx_rcnn_tpu import telemetry\n"
        "from mx_rcnn_tpu.telemetry.obs import write_snapshot\n"
        "telemetry.configure(sys.argv[1], rank=1, world=2)\n"
        "telemetry.get().counter('train/steps', 11)\n"
        "assert write_snapshot() is not None\n"
        "telemetry.shutdown()\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", peer_prog, d],
                       capture_output=True, text=True, env=env,
                       timeout=120)
    assert r.returncode == 0, r.stderr

    telemetry.configure(d, rank=0, world=2)
    telemetry.get().counter("train/steps", 13)
    srv = ObsServer(0, telemetry_dir=d)
    try:
        _, body = http_get(srv.port, "/metrics")
        assert 'mxr_train_steps_total{rank="0"} 13' in body
        assert 'mxr_train_steps_total{rank="1"} 11' in body
    finally:
        srv.close()


def test_peer_snapshot_reader_skips_own_rank_and_garbage(tmp_path):
    d = str(tmp_path)
    peer = Telemetry(d, rank=1, world=2, stream=False)
    peer.counter("c", 1)
    write_snapshot(peer)
    peer.close()
    with open(os.path.join(d, "snapshot_rank2.json"), "w") as f:
        f.write("{half a json")  # a peer dying mid-publish must not 500
    per_rank, ages = read_peer_snapshots(d, skip_rank=1)
    assert per_rank == {} and ages == {}
    per_rank, _ = read_peer_snapshots(d)
    assert list(per_rank) == [1]


def test_obs_plane_lifecycle_and_inertness(tmp_path):
    # port unset → fully inert: no sink, no threads, no excepthook swap
    hook = sys.excepthook
    plane = ObsPlane(port=0, telemetry_dir="", rank=0, world=1)
    assert not plane.active and plane.server is None
    assert not telemetry.get().enabled
    assert sys.excepthook is hook
    plane.close()

    # port set → owns an in-stream sink, serves, writes summary on close
    plane = ObsPlane(port=free_port(), telemetry_dir=str(tmp_path),
                     rank=0, world=1, run_meta={"driver": "test_obs"})
    try:
        assert plane.owns_sink and telemetry.get().enabled
        assert sys.excepthook is not hook
        telemetry.get().counter("train/steps", 3)
        _, body = http_get(plane.server.port, "/metrics")
        assert 'mxr_train_steps_total{rank="0"} 3' in body
    finally:
        plane.close()
    assert not telemetry.get().enabled  # plane shut its own sink down
    assert sys.excepthook is hook
    summary = json.load(open(tmp_path / "summary.json"))
    assert summary["counters"]["train/steps"] == 3
    # the final snapshot from the writer's stop() is on disk too
    assert (tmp_path / "snapshot_rank0.json").exists()


# -- flight recorder -------------------------------------------------------


def flight_events(path):
    events = [json.loads(line) for line in open(path)]  # all valid JSONL
    assert all("kind" in e and "t" in e for e in events)
    return events


def test_flight_ring_bound_and_trigger(tmp_path):
    tel = Telemetry(str(tmp_path), rank=0, ring_size=8)
    for i in range(40):
        tel.counter("c")
    path = tel.dump_flight("test_reason", detail=7)
    tel.close()
    assert path == str(tmp_path / "flight_0.jsonl")
    events = flight_events(path)
    assert len(events) <= 8  # ring bound holds (trigger included)
    last = events[-1]
    assert last["kind"] == "meta" and last["name"] == "flight_trigger"
    assert last["fields"] == {"reason": "test_reason", "detail": 7}


def test_flight_dump_without_dir_is_none():
    tel = Telemetry("", rank=0, stream=False)
    tel.counter("c")
    assert tel.dump_flight("nowhere") is None
    tel.close()
    assert telemetry.NULL.dump_flight("ignored") is None


def test_nan_halt_dumps_flight(tmp_path):
    cfg, _, loader = tiny_data(n_images=8)
    model, params = tiny_model(cfg)
    tel_dir = tmp_path / "tel"
    with pytest.raises(NonFiniteLossError, match="policy=halt"):
        fit(cfg, model, params, NanBatchLoader(loader, 1),
            begin_epoch=0, end_epoch=1, prefix=str(tmp_path / "ck"),
            frequent=1, telemetry_dir=str(tel_dir),
            resilience=ResilienceOptions(nan_policy="halt"))
    events = flight_events(tel_dir / "flight_0.jsonl")
    assert len(events) <= RING_SIZE
    last = events[-1]
    assert last["name"] == "flight_trigger"
    assert last["fields"]["reason"] == "nan_detected"
    assert last["fields"]["policy"] == "halt"
    # the ring holds the run's tail: the nan counter/meta land just before
    names = [e["name"] for e in events]
    assert "nan_detected" in names and "train/nan_detected" in names


def test_sigterm_dumps_flight(tmp_path):
    cfg, _, loader = tiny_data(n_images=8)
    model, params = tiny_model(cfg)
    tel_dir = tmp_path / "tel"
    fit(cfg, model, params, SignalAtBatchLoader(loader, 2),
        begin_epoch=0, end_epoch=2, prefix=str(tmp_path / "ck"),
        frequent=1, telemetry_dir=str(tel_dir),
        resilience=ResilienceOptions(auto_resume=True,
                                     save_every_n_steps=100))
    events = flight_events(tel_dir / "flight_0.jsonl")
    assert len(events) <= RING_SIZE
    # the handler's immediate dump is superseded by the step-boundary one,
    # so the final events explain the shutdown in order: signal → boundary
    last = events[-1]
    assert last["name"] == "flight_trigger"
    assert last["fields"]["reason"] == "preempted"
    sigs = [e for e in events if e["name"] == "flight_trigger"
            and e["fields"]["reason"] == "preempt_signal"]
    assert sigs and sigs[0]["fields"]["signal"] == "SIGTERM"


# -- trace export ----------------------------------------------------------


def test_trace_export_nested_spans_roundtrip(tmp_path):
    tel = Telemetry(str(tmp_path), rank=0, trace=True)
    with tel.span("train/epoch"):
        with tel.span("train/dispatch"):
            pass
        with tel.span("train/dispatch"):
            pass
    tel.counter("train/steps", 2)
    tel.gauge("loader/queue_depth", 4.0)
    tel.add("loader/worker0/produce", 0.01)
    tel.meta("flight_trigger", reason="unit")
    tel.close()
    events = [json.loads(line)
              for line in open(tmp_path / "events_rank0.jsonl")]
    doc = json.loads(json.dumps(chrome_trace(events)))  # round-trips
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    outer = next(e for e in xs if e["name"] == "train/epoch")
    inners = [e for e in xs if e["name"] == "train/dispatch"]
    assert len(inners) == 2
    for e in inners:  # nested inside the epoch span, same track
        assert e["pid"] == outer["pid"] and e["tid"] == outer["tid"]
        assert outer["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    # worker spans get their own named track
    worker = next(e for e in xs if e["name"] == "loader/worker0/produce")
    assert worker["tid"] != outer["tid"]
    meta_names = {m["args"]["name"] for m in evs if m["ph"] == "M"}
    assert "rank 0" in meta_names and "worker0" in meta_names
    # counters/gauges plot; meta becomes an instant crash marker
    assert any(e["ph"] == "C" and e["name"] == "train/steps" for e in evs)
    assert any(e["ph"] == "i" and e["name"] == "flight_trigger"
               for e in evs)


def test_trace_spans_without_ts_derive_start(tmp_path):
    tel = Telemetry(str(tmp_path), rank=0)  # trace off: no "ts" field
    tel.add("train/dispatch", 2.0)
    tel.close()
    events = [json.loads(line)
              for line in open(tmp_path / "events_rank0.jsonl")]
    assert all("ts" not in e for e in events if e["kind"] == "span")
    xs = [e for e in chrome_trace(events)["traceEvents"]
          if e["ph"] == "X"]
    assert len(xs) == 1 and xs[0]["dur"] == pytest.approx(2e6)


def test_report_cli_trace_flag(tmp_path):
    tel = Telemetry(str(tmp_path / "tel"), rank=0)
    with tel.span("eval/forward"):
        pass
    tel.counter("eval/images", 4)
    tel.close()
    out = tmp_path / "trace.json"
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "telemetry_report.py"),
         str(tmp_path / "tel"), "--trace", str(out)],
        capture_output=True, text=True, cwd=str(REPO), timeout=120)
    assert r.returncode == 0, r.stderr
    doc = json.load(open(out))
    assert len(doc["traceEvents"]) > 0


# -- serve frontend content negotiation ------------------------------------


def test_serve_metrics_content_negotiation(tmp_path):
    from mx_rcnn_tpu.serve import make_server, unix_http_request

    from .test_serve import make_engine, tiny_cfg

    engine = make_engine(tiny_cfg()).start()
    sock = str(tmp_path / "serve.sock")
    server = make_server(engine, unix_socket=sock)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        # default stays JSON for existing callers
        status, doc = unix_http_request(sock, "GET", "/metrics")
        assert status == 200 and isinstance(doc, dict)
        assert "counters" in doc and "queue_depth" in doc
        # ?format=prom negotiates the text exposition
        status, text = unix_http_request(sock, "GET",
                                         "/metrics?format=prom")
        assert status == 200 and isinstance(text, str)
        assert 'mxr_serve_requests_total{rank="0"} 0' in text
        assert 'mxr_serve_queue_depth{rank="0",stat="last"} 0' in text
        # Accept: text/plain too
        status, text2 = unix_http_request(
            sock, "GET", "/metrics", headers={"Accept": "text/plain"})
        assert status == 200 and "mxr_serve_requests_total" in text2
        # /predict and /healthz untouched by the negotiation change
        status, health = unix_http_request(sock, "GET", "/healthz")
        assert status == 200 and health["status"] == "ok"
    finally:
        server.shutdown()
        server.server_close()
        engine.stop()
