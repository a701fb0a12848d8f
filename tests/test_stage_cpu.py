"""Every stage clock also counts its thread's CPU seconds and minor page
faults, and ``/metrics`` the process's CPU and cores (CPU, tiny sizes).

``telemetry.stage`` reads ``getrusage(RUSAGE_THREAD)`` at its two ends: a
stage that spins books about its wall time as CPU, one that sleeps or waits
for a lock about none.  The engine books the dispatcher's CPU into
``serve/service_time`` from the instants of its seconds, times the copy into
the staging row (``serve/stage_row``) and the reply (``frontend/reply``), and
never leaves a stage on another thread than the one that entered it.
"""

import collections
import mmap
import threading
import time

import numpy as np
import pytest

from benchmark import harness
from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.serve import (encode_image_payload, make_server,
                               unix_http_request)
from mx_rcnn_tpu.telemetry import Hist
from tests.test_serve import make_engine, raw_image, tiny_cfg

BENCH = harness.load_json(harness.ROOT + "/BENCHMARK.json")
# the thread's CPU seconds advance at the scheduler's tick (4 ms at HZ=250,
# 10 ms on the chip's host): one use may read up to a tick over its wall
TICK = 0.01


def spin(seconds):
    t_end = time.perf_counter() + seconds
    while time.perf_counter() < t_end:
        pass


# -- the helper --------------------------------------------------------------


def test_a_stage_that_spins_books_its_wall_as_cpu_and_one_that_sleeps_none():
    busy, idle = Hist(), Hist()
    with telemetry.stage("unit/spin", busy) as spun:
        spin(0.2)
    with telemetry.stage("unit/sleep", idle) as slept:
        time.sleep(0.2)
    # on a loaded box a spinning thread may lose some of its time to others
    assert 0.5 * spun.seconds <= spun.cpu_seconds <= spun.seconds + TICK
    assert slept.seconds >= 0.2 and slept.cpu_seconds <= TICK
    assert busy.cpu_sum == spun.cpu_seconds and idle.cpu_sum == \
        slept.cpu_seconds
    assert busy.clock() == {"count": 1, "sum_s": spun.seconds,
                            "cpu_s": spun.cpu_seconds,
                            "minflt": spun.minflt}


def test_a_stage_waiting_for_a_lock_a_busy_thread_holds_books_wall_not_cpu():
    """What a stage that waits for the GIL looks like, with a lock in the
    GIL's place: the holder spins, the waiter's clock runs, its CPU not."""
    lock, held = threading.Lock(), threading.Event()

    def holder():
        with lock:
            held.set()
            spin(0.3)

    th = threading.Thread(target=holder)
    th.start()
    assert held.wait(10)
    with telemetry.stage("unit/wait") as waited:
        with lock:
            pass
    th.join()
    assert waited.seconds >= 0.2
    assert waited.cpu_seconds < 0.1 * waited.seconds


def test_book_carries_the_summed_cpu_and_faults_and_a_fresh_array_faults():
    st = telemetry.stage("unit/fresh")
    for _ in range(2):
        with st:
            # 64 MB mapped fresh from the OS (not through an allocator that
            # may hand back memory it kept) and a byte of every page written:
            # one fault a page, or one a huge page where those are on, so
            # no count of 4-KiB pages
            fresh = mmap.mmap(-1, 64 << 20)
            np.frombuffer(fresh, np.uint8)[::4096] = 1
            fresh.close()
    nothing = telemetry.stage("unit/nothing")
    with nothing:
        pass
    assert st.uses == 2 and st.minflt > nothing.minflt >= 0
    h = Hist()
    st.book(h)
    assert h.count == 1 and h.sum == st.seconds
    assert h.cpu_sum == st.cpu_seconds > 0 and h.minflt == st.minflt


def test_thread_usage_is_the_calling_threads_own():
    out = {}

    def other():
        out["before"] = telemetry.thread_usage()[0]
        spin(0.2)
        out["after"] = telemetry.thread_usage()[0]

    mine = telemetry.thread_usage()[0]
    th = threading.Thread(target=other)
    th.start()
    th.join()
    assert out["after"] - out["before"] >= 0.1
    # this thread only joined: it did not run the other's 0.2 s
    assert telemetry.thread_usage()[0] - mine < 0.1


# -- the engine's clocks (fake predictor, over HTTP) -------------------------


@pytest.fixture
def over_http(tmp_path):
    """An engine over the fake predictor behind a Unix-socket HTTP server:
    one full batch of two a turn -> (engine, post(img))."""
    engine = make_engine(tiny_cfg(), batch_size=2,
                         max_delay_ms=20000.0).start()  # full batches only
    sock = str(tmp_path / "serve.sock")
    server = make_server(engine, unix_socket=sock)
    th = threading.Thread(target=server.serve_forever, daemon=True)
    th.start()

    def post(img):
        status, resp = unix_http_request(
            sock, "POST", "/predict", encode_image_payload(img), timeout=60)
        assert status == 200, resp
        return resp

    try:
        yield engine, post
    finally:
        server.shutdown()
        server.server_close()
        engine.stop()


def quiet(engine):
    assert engine.drain(timeout=30)
    engine.resume()
    return engine.metrics()


def burst(post, n, value=40):
    """``n`` requests at once, in pairs that fill a batch each."""
    threads = [threading.Thread(target=post,
                                args=(raw_image(60, 100, value + 7 * i),))
               for i in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(60)
        assert not th.is_alive()


def test_no_stage_of_the_server_is_left_on_another_thread(over_http,
                                                          monkeypatch):
    engine, post = over_http
    entered = collections.defaultdict(list)
    crossed, lock = [], threading.Lock()
    enter, leave = telemetry.stage.__enter__, telemetry.stage.__exit__

    def checked_enter(self):
        with lock:
            entered[id(self)].append((threading.get_ident(), self.name))
        return enter(self)

    def checked_exit(self, *exc):
        with lock:
            if entered[id(self)].pop()[0] != threading.get_ident():
                crossed.append(self.name)
        return leave(self, *exc)

    monkeypatch.setattr(telemetry.stage, "__enter__", checked_enter)
    monkeypatch.setattr(telemetry.stage, "__exit__", checked_exit)
    burst(post, 6)
    m = quiet(engine)

    def still_open():
        with lock:
            return [n for v in entered.values() for _, n in v]

    # a client has its answer before the reply's clock stops: every use is
    # left soon after, but the dispatcher's wait for work that is under way
    t_end = time.monotonic() + 10
    while still_open() not in ([], ["serve/idle"]) \
            and time.monotonic() < t_end:
        time.sleep(0.01)
    monkeypatch.undo()
    assert m["counters"]["served"] == 6
    assert not crossed, crossed
    assert still_open() in ([], ["serve/idle"])


def test_metrics_carry_cpu_and_faults_that_never_decrease(over_http):
    engine, post = over_http
    snaps = []
    for n in (4, 2):
        burst(post, n)
        time.sleep(0.3)          # an idle period between the bursts
        snaps.append(quiet(engine))
    first, second = snaps
    for m, served in ((first, 4), (second, 6)):
        assert m["counters"]["served"] == served
        assert "staged_rows" not in m["counters"]    # the clock's count
        for name in ("serve/stage_row", "frontend/reply",
                     "serve/host_prep"):
            assert m["stages"][name]["count"] == served, name
        for doc in m["stages"].values():
            assert set(doc) == {"count", "sum_s", "cpu_s", "minflt"}
            assert 0 <= doc["cpu_s"] <= doc["sum_s"] + TICK * doc["count"]
        assert m["host"]["cores"] >= 1 and m["host"]["cpu_s"] > 0
    assert set(first["stages"]) == set(second["stages"])
    for name, doc in first["stages"].items():
        for key in ("count", "sum_s", "cpu_s", "minflt"):
            assert second["stages"][name][key] >= doc[key], (name, key)
    assert second["host"]["cpu_s"] >= first["host"]["cpu_s"]
    turns = second["stages"]["serve/service_time"]
    assert turns["count"] == 3 and 0 <= turns["cpu_s"] <= turns["sum_s"] + 3 * TICK
    # the dispatcher's wait for work does not run: it reads near 0 CPU
    idle = second["stages"]["serve/idle"]   # the one under way not booked
    assert idle["sum_s"] >= 0.25
    assert idle["cpu_s"] <= 0.1 * idle["sum_s"] + TICK


def test_the_readers_on_the_tiny_engine_stay_under_their_wall_clocks(
        over_http):
    engine, post = over_http
    # a device that takes 50 ms a batch: the turn waits for it off the CPU
    engine.predictor.delay_s = 0.05
    burst(post, 2)
    before = quiet(engine)
    burst(post, 8)
    after = quiet(engine)
    names = ("turn_oncpu_ms", "turn_post_oncpu_ms", "request_oncpu_ms",
             "host_prep_minflt", "stage_row_ms", "frontend_reply_ms",
             "host_cores_busy", "turn_ms", "turn_postprocess_ms",
             "frontend_decode_ms", "host_prep_ms")
    got = {k: v["value"] for k, v in harness.read_layers(
        {"per_layer": [m for m in BENCH["per_layer"] if m["name"] in names]},
        "c4-serve-closed",
        {"metrics_before": before, "metrics_after": after}).items()}
    assert set(got) == set(names)
    tick_ms = 1e3 * TICK
    assert got["turn_ms"] >= 50
    assert 0 <= got["turn_oncpu_ms"] <= 0.5 * got["turn_ms"] + tick_ms
    assert 0 <= got["turn_post_oncpu_ms"] <= got["turn_postprocess_ms"] \
        + tick_ms
    assert 0 < got["host_cores_busy"] <= after["host"]["cores"]
    # the request threads' CPU a request lies inside their five clocks
    walls = sum(got[k] for k in ("frontend_decode_ms", "host_prep_ms",
                                 "stage_row_ms", "frontend_reply_ms"))
    read = after["stages"]["frontend/read"]
    read0 = before["stages"]["frontend/read"]
    walls += 1e3 * (read["sum_s"] - read0["sum_s"]) / 8
    assert 0 <= got["request_oncpu_ms"] <= walls + 5 * tick_ms
    # the threads the readers see are part of the process
    dt = after["t_s"] - before["t_s"]
    turns = after["stages"]["serve/service_time"]["count"] \
        - before["stages"]["serve/service_time"]["count"]
    dispatcher = got["turn_oncpu_ms"] * turns / 1e3 / dt
    requests = got["request_oncpu_ms"] * 8 / 1e3 / dt
    assert dispatcher + requests <= got["host_cores_busy"] \
        + (turns + 5 * 8 + 1) * TICK / dt
