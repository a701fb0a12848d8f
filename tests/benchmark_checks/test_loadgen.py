"""The generator's arithmetic on synthetic schedules, against a stub HTTP
server on a Unix socket (no model): all four arrival modes, a stall, a
failure, lateness."""

import json
import os
import socketserver
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler

import pytest

from benchmark import harness, loadgen

MIX = {"pool": 4, "short": [8, 10], "long": [12, 14], "portrait_every": 2}
GOOD = {"detections": [{"cls": 3, "score": 0.5,
                        "bbox": [1.0, 2.0, 3.0, 4.0]}],
        "queue_wait_ms": 1.5}


class Stub(socketserver.ThreadingMixIn, socketserver.UnixStreamServer):
    daemon_threads = True

    def __init__(self, path, behave):
        self.behave, self.count, self.lock = behave, 0, threading.Lock()
        super().__init__(path, Handler)


class Handler(BaseHTTPRequestHandler):
    def log_message(self, *a):
        pass

    def _answer(self, status, doc):
        body = json.dumps(doc).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def address_string(self):
        return "stub"

    def do_GET(self):
        self._answer(200, {"ready": True})

    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        with self.server.lock:
            i = self.server.count
            self.server.count += 1
        status, doc, delay = self.server.behave(i)
        time.sleep(delay)
        self._answer(status, doc)


@pytest.fixture
def stub(tmp_path):
    servers = []

    def start(behave):
        path = str(tmp_path / f"s{len(servers)}.sock")
        srv = Stub(path, behave)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        servers.append(srv)
        return path

    yield start
    for srv in servers:
        srv.shutdown()
        srv.server_close()


def window(sock, traffic, seconds, seed=3):
    bodies = loadgen.make_bodies(MIX, seed)
    t0 = time.monotonic() + 0.05
    if traffic["arrival"] == "closed":
        order = loadgen.body_order(256, len(bodies), seed)
        reqs, threads = loadgen.run_closed(sock, traffic["clients"], order,
                                           bodies, 81, t0, seconds, 10.0)
    else:
        offs = loadgen.arrival_offsets(traffic, seconds, seed)
        order = loadgen.body_order(len(offs), len(bodies), seed)
        reqs, threads = loadgen.run_open(sock, offs, order, bodies, 81, t0,
                                         10.0)
    time.sleep(max(t0 + seconds - time.monotonic(), 0))
    for th in threads:
        th.join(10.0)
    assert not any(th.is_alive() for th in threads)
    return loadgen.summarize(list(reqs), t0, seconds, 99999.0,
                             traffic["arrival"] == "closed"), list(reqs)


@pytest.mark.parametrize("arrival", ["poisson", "uniform", "burst"])
def test_open_arrivals_offer_the_fixed_work(stub, arrival):
    sock = stub(lambda i: (200, GOOD, 0.005))
    traffic = {"arrival": arrival, "rate": 50.0, "burst": 5}
    out, reqs = window(sock, traffic, 1.0)
    assert out["attempted"] == 50 and out["failed"] == 0
    assert out["serve_imgs_per_s"] == 50.0
    assert 5.0 <= out["serve_p50_ms"] < 200.0
    assert out["queue_wait_ms"] == 1.5
    assert out["loadgen_late_ms"] is not None and out["loadgen_late_ms"] >= 0
    # every body of the pool is used, equally often within a turn
    assert {r.body for r in reqs} == set(range(MIX["pool"]))


def test_poisson_gaps_are_one_multiset_for_every_seed():
    tr = {"arrival": "poisson", "rate": 40.0}
    a = loadgen.arrival_offsets(tr, 10.0, 1)
    b = loadgen.arrival_offsets(tr, 10.0, 2 ** 31 + 17)
    assert len(a) == len(b) == 400 and a != b
    gaps = lambda o: sorted(round(y - x, 9) for x, y in zip(o, o[1:]))
    # the same gaps in another order (the first, halved, leads each list)
    assert abs(sum(gaps(a)) - sum(gaps(b))) < 0.2
    assert max(a) < 10.0 and max(b) < 10.0
    assert loadgen.body_sizes(MIX) == loadgen.body_sizes(dict(MIX))


def test_closed_loop_keeps_clients_busy(stub):
    sock = stub(lambda i: (200, GOOD, 0.05))
    out, reqs = window(sock, {"arrival": "closed", "clients": 4}, 1.0)
    # 4 clients x 1 s / 50 ms: about 80 requests, none failed
    assert 50 <= out["attempted"] <= 84 and out["failed"] == 0
    assert 50.0 <= out["serve_p50_ms"] < 150.0
    # the rate counts answers that ended in the window: the (at most four)
    # still in flight at its close were attempted, and are not in the rate
    ended = out["serve_imgs_per_s"] * 1.0
    assert out["attempted"] - 4 <= ended <= out["attempted"]


def test_a_stall_moves_p95_because_latency_runs_from_the_due_time(stub):
    # the stub serialises: every request waits for a shared lock, and the
    # tenth holds it for 2 s — those behind it were due long before they end
    gate = threading.Lock()

    def behave(i):
        with gate:
            time.sleep(2.0 if i == 10 else 0.002)
        return 200, GOOD, 0.0

    sock = stub(behave)
    out, _ = window(sock, {"arrival": "uniform", "rate": 40.0}, 1.5)
    assert out["failed"] == 0
    assert out["serve_p95_ms"] > 1500.0          # the stall is in the tail
    calm, _ = window(stub(lambda i: (200, GOOD, 0.002)),
                     {"arrival": "uniform", "rate": 40.0}, 1.5)
    assert calm["serve_p95_ms"] < 300.0


def test_a_failure_counts_as_a_miss_not_as_a_fast_answer(stub):
    def behave(i):
        if i % 5 == 0:
            return 503, {"error": "shed"}, 0.0
        if i % 5 == 1:
            return 200, {"detections": [{"cls": 0, "score": 2.0}]}, 0.0
        return 200, GOOD, 0.002

    out, _ = window(stub(behave), {"arrival": "uniform", "rate": 50.0}, 1.0)
    assert out["attempted"] == 50 and out["failed"] == 20
    assert out["status"] == {"503": 10, "200_malformed": 10, "200": 30}
    assert out["serve_imgs_per_s"] == 30.0
    assert out["serve_p95_ms"] == 99999.0        # 40 % missed: p95 is a miss
    assert out["serve_p50_ms"] < 1000.0


def test_percentile_is_nearest_rank():
    vals = sorted(float(v) for v in range(1, 101))
    assert loadgen.percentile(vals, 0.5) == 50.0
    assert loadgen.percentile(vals, 0.95) == 95.0
    assert loadgen.percentile([7.0], 0.95) == 7.0


def test_the_child_process_end_to_end(stub):
    """``loadgen.py`` as the harness starts it: spec on stdin, events on
    stdout, the sample with the largest body in it; it never loads jax."""
    sock = stub(lambda i: (200, GOOD, 0.003))
    spec = {"traffic": {"arrival": "burst", "rate": 30.0, "burst": 3,
                        "bodies": MIX, "warm_per_orientation": 2,
                        "sample": 3, "drain_s": 5.0},
            "socket": sock, "seed": 2 ** 31 + 5, "seconds": 1.0,
            "num_classes": 81}
    probe = ("import sys, runpy, atexit; sys.argv = ['loadgen']; "
             "atexit.register(lambda: sys.stderr.write("
             "'JAX_LOADED=' + str('jax' in sys.modules))); "
             "runpy.run_path(" + repr(os.path.join(harness.HERE, "loadgen.py"))
             + ", run_name='__main__')")
    proc = subprocess.run([sys.executable, "-c", probe], text=True,
                          input=json.dumps(spec) + "\n", capture_output=True,
                          timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "JAX_LOADED=False" in proc.stderr
    events = [json.loads(line) for line in proc.stdout.splitlines()]
    assert [e["event"] for e in events] == ["window", "closed", "result"]
    res = events[-1]
    assert res["attempted"] == 30 and res["failed"] == 0
    assert 3 <= len(res["sample"]) <= 4
    sizes = [s["doc"]["shape"][0] * s["doc"]["shape"][1]
             for s in res["sample"]]
    pool = [h * w for h, w in loadgen.body_sizes(MIX)]
    assert max(sizes) == max(pool)
