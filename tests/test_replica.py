"""Multi-replica serving plane tests (ISSUE 8).

Three layers, mirroring the subsystem split:

* **Supervisor state machine** — deterministic unit tests with injected
  clock (``poll(now=...)``, the ``SLOController.tick`` pattern), fake
  procs, fake probes, fake reloads: ready transitions, crash → backoff
  schedule, systemic respawn limit, hang detection, router retry budget,
  rolling reload + rollback, generation monotonicity under crash.
* **Replica-side machinery** — checkpoint scanning/watching, fault-env
  parsing, the zero-downtime swap with canary rollback on a live engine
  (fake predictor, so no XLA in the loop).
* **End-to-end chaos** — a REAL supervisor + router over REAL
  subprocesses (``tests/replica_worker.py``): kill -9 one of two
  replicas mid-burst and observe failover + respawn; roll a hot reload
  through the plane under traffic with zero dropped 2xx-eligible
  requests.
"""

import dataclasses
import itertools
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from mx_rcnn_tpu.serve import replica as rp
from mx_rcnn_tpu.serve import supervisor as sv
from mx_rcnn_tpu.serve import (RejectedError, ReplicaRouter, ServeEngine,
                               ServeOptions, encode_image_payload, warmup)
from tests.faults import replica_fault_env
from tests.replica_worker import FakeServePredictor
from tests.test_serve import make_engine, raw_image, tiny_cfg

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "replica_worker.py")


# -- fakes ------------------------------------------------------------------


class FakeProc:
    """subprocess.Popen stand-in the supervisor can poll/kill/wait."""

    _pids = itertools.count(1000)

    def __init__(self, stubborn=False):
        self.pid = next(FakeProc._pids)
        self.returncode = None
        self.killed = False
        self.terminated = False
        self.stubborn = stubborn  # ignores SIGTERM (needs the kill path)

    def poll(self):
        return self.returncode

    def die(self, rc=1):
        self.returncode = rc

    def kill(self):
        self.killed = True
        self.returncode = -9

    def terminate(self):
        self.terminated = True
        if not self.stubborn:
            self.returncode = -15

    def wait(self, timeout=None):
        if self.returncode is None:
            raise subprocess.TimeoutExpired("fake", timeout)
        return self.returncode


def _specs(n, sock_dir="/tmp/mxr_fake_socks"):
    return [sv.ReplicaSpec(argv=["serve.py"],
                           sock=os.path.join(sock_dir, f"r{i}.sock"),
                           index=i) for i in range(n)]


class Harness:
    """A supervisor over fake procs with scriptable probes/reloads."""

    def __init__(self, n=2, stubborn=False, specs=None, **opt_kw):
        self.procs = {}           # index -> [FakeProc, ...] (respawns)
        self.ready = {}           # index -> /readyz answers 200
        self.healthy = {}         # index -> /healthz status | Exception
        self.reloads = []         # (index, target) in call order
        self.reload_status = 200  # int, or callable(handle, target) -> int
        self._stubborn = stubborn

        def spawn(spec):
            p = FakeProc(stubborn=self._stubborn)
            self.procs.setdefault(spec.index, []).append(p)
            return p

        def probe(handle, path):
            if path == "/readyz":
                return (200 if self.ready.get(handle.index) else 503), {}
            st = self.healthy.get(handle.index, 200)
            if isinstance(st, Exception):
                raise st
            return st, {}

        def reload_fn(handle, target):
            self.reloads.append((handle.index, dict(target)))
            st = (self.reload_status(handle, target)
                  if callable(self.reload_status) else self.reload_status)
            if st == 200:
                return st, {"generation": target.get("generation"),
                            "recompiles_during_swap": 0}
            return st, {"error": "canary failed: injected"}

        self.sup = sv.ReplicaSupervisor(
            specs if specs is not None else _specs(n),
            sv.SupervisorOptions(**opt_kw),
            spawn_fn=spawn, probe_fn=probe, reload_fn=reload_fn)

    def proc(self, i):
        return self.procs[i][-1]

    def up(self, n=None, now=1.0):
        """spawn_all + mark every replica ready + one poll."""
        self.sup.spawn_all(now=0.0)
        for i in range(n if n is not None else len(self.sup.handles)):
            self.ready[i] = True
        self.sup.poll(now=now)


TARGET = {"prefix": "/ck", "kind": "epoch", "epoch": 3, "consumed": 0}


# -- supervisor state machine ----------------------------------------------


def test_token_bucket_budget_and_refill():
    tb = sv.TokenBucket(2, 1.0)
    assert tb.take(now=0.0) and tb.take(now=0.0)
    assert not tb.take(now=0.0)          # burst capacity spent
    assert tb.take(now=1.0)              # 1 token refilled
    assert not tb.take(now=1.0)
    assert tb.take(now=100.0) and tb.take(now=100.0)
    assert not tb.take(now=100.0)        # refill is capped at capacity


def test_build_child_argv_strips_parent_flags():
    argv = ["serve.py", "--model", "m.npz", "--port", "8000",
            "--host=0.0.0.0", "--replicas", "2",
            "--watch-checkpoints", "/ckpts", "--watch-interval-s", "2",
            "--replica-devices", "0;1", "--serve-batch", "4"]
    out = sv.build_child_argv(argv, "/tmp/r0.sock", 0)
    assert out[0] == sys.executable and out[1] == "serve.py"
    joined = " ".join(out)
    for flag in ("--port", "--host", "--watch-checkpoints",
                 "--watch-interval-s", "--replica-devices"):
        assert flag not in joined
    assert "--model m.npz" in joined          # model flags pass through
    assert "--replicas 2" in joined           # kept: obs world size
    assert "--serve-batch 4" in joined
    assert out[-4:] == ["--unix-socket", "/tmp/r0.sock",
                        "--replica-index", "0"]


def test_replica_specs_device_groups(tmp_path):
    sp = sv.replica_specs(["serve.py", "--model", "m"], 3, str(tmp_path),
                          devices="0,1;2,3")
    assert [s.index for s in sp] == [0, 1, 2]
    assert sp[0].env["MXR_REPLICA_DEVICES"] == "0,1"
    assert sp[1].env["MXR_REPLICA_DEVICES"] == "2,3"
    assert "MXR_REPLICA_DEVICES" not in sp[2].env  # no group for it
    assert sp[1].env["MXR_REPLICA_INDEX"] == "1"
    assert sp[0].sock.endswith("replica_0.sock")


def test_ready_transition_and_slow_starter_not_killed():
    hz = Harness(n=2)
    sup = hz.sup
    sup.spawn_all(now=0.0)
    sup.poll(now=1.0)  # alive, /readyz 503: warming, not dead
    assert all(h.state == sv.STARTING for h in sup.handles)
    assert sup.ready_count() == 0
    hz.ready[0] = True
    sup.poll(now=2.0)
    assert sup.handles[0].state == sv.READY and sup.handles[0].routable
    assert sup.handles[1].state == sv.STARTING  # still warming — alive
    assert sup.ready_count() == 1


def test_start_timeout_kills_and_backoffs():
    hz = Harness(n=1, start_timeout_s=10.0)
    hz.sup.spawn_all(now=0.0)
    hz.sup.poll(now=11.0)
    assert hz.proc(0).killed
    assert hz.sup.handles[0].state == sv.BACKOFF


def test_crash_respawn_exponential_backoff_schedule():
    hz = Harness(n=1, backoff_base_s=0.5, backoff_max_s=4.0,
                 max_respawns=100)
    sup, h = hz.sup, hz.sup.handles[0]
    now = 0.0
    sup.spawn_all(now=now)
    delays = []
    for _ in range(5):
        hz.proc(0).die(9)
        sup.poll(now=now)
        assert h.state == sv.BACKOFF
        delays.append(h.next_spawn_t - now)
        sup.poll(now=h.next_spawn_t - 0.01)   # not yet eligible
        assert h.state == sv.BACKOFF
        now = h.next_spawn_t
        sup.poll(now=now)                     # eligible: respawn
        assert h.state == sv.STARTING
    assert delays == [0.5, 1.0, 2.0, 4.0, 4.0]  # doubles, then capped
    assert sup.counters["respawn"] == 5


def test_systemic_limit_fails_replica_and_breaks_plane():
    hz = Harness(n=1, backoff_base_s=0.0, max_respawns=2)
    sup, h = hz.sup, hz.sup.handles[0]
    sup.spawn_all(now=0.0)
    now = 0.0
    while h.state != sv.FAILED:
        hz.proc(0).die(9)
        now += 1.0
        sup.poll(now=now)  # declare dead (and respawn if under the limit)
        now += 1.0
        sup.poll(now=now)
    assert len(hz.procs[0]) == 3               # initial + 2 respawns
    assert sup.counters["systemic"] == 1
    assert sup.broken.is_set()                 # every replica FAILED
    sup.poll(now=now + 100.0)                  # FAILED is terminal
    assert h.state == sv.FAILED and len(hz.procs[0]) == 3


def test_hang_detection_probe_timeouts_then_kill():
    hz = Harness(n=1, hang_probes=3)
    sup, h = hz.sup, hz.sup.handles[0]
    hz.up(now=1.0)
    assert h.state == sv.READY
    hz.healthy[0] = TimeoutError("probe timed out")
    sup.poll(now=2.0)
    sup.poll(now=3.0)
    assert h.state == sv.READY and h.probe_fails == 2  # not yet hung
    sup.poll(now=4.0)                                   # third miss
    assert hz.proc(0).killed and h.state == sv.BACKOFF
    assert sup.counters["hang_kill"] == 1


def test_stable_ready_resets_backoff_and_suspect_clears():
    hz = Harness(n=1, stable_s=10.0)
    sup, h = hz.sup, hz.sup.handles[0]
    sup.spawn_all(now=0.0)
    hz.proc(0).die(1)
    sup.poll(now=0.0)                 # failures = 1
    sup.poll(now=h.next_spawn_t)      # respawn
    hz.ready[0] = True
    sup.poll(now=1.0)                 # ready at t=1
    assert h.state == sv.READY and h.failures == 1
    sup.note_suspect(h)
    assert not h.routable
    sup.poll(now=2.0)                 # healthy probe clears the suspicion
    assert h.routable and h.failures == 1   # too soon to forgive backoff
    sup.poll(now=20.0)                # stable past stable_s
    assert h.failures == 0


def test_sweep_terminates_children_and_unlinks_sockets(tmp_path):
    specs = [sv.ReplicaSpec(argv=["x"],
                            sock=str(tmp_path / f"r{i}.sock"),
                            index=i) for i in range(2)]
    hz = Harness(specs=specs, stubborn=True)
    hz.up()
    for s in specs:
        open(s.sock, "w").close()
    hz.sup.sweep(graceful_timeout=0.0)
    for h in hz.sup.handles:
        assert h.state == sv.STOPPED and not h.routable
    for i in range(2):
        assert hz.proc(i).terminated          # graceful first...
        assert hz.proc(i).killed              # ...then the hard kill
        assert not os.path.exists(specs[i].sock)
    hz.sup.sweep(graceful_timeout=0.0)        # idempotent


# -- router: retry-once, budget, degradation -------------------------------


def test_router_no_ready_replicas_sheds_early():
    hz = Harness(n=2)  # spawned never → nothing routable
    router = ReplicaRouter(hz.sup, forward_fn=None)
    status, raw, ctype = router.route_predict(b"{}")
    assert status == 503 and b"no ready replicas" in raw
    assert hz.sup.counters["no_ready"] == 1


def test_router_retries_transport_error_on_alternate():
    hz = Harness(n=2)
    hz.up()
    calls = []

    def fwd(h, method, path, body, timeout):
        calls.append(h.index)
        if len(calls) == 1:
            raise ConnectionRefusedError("replica died")
        return 200, b'{"ok":1}', "application/json"

    router = ReplicaRouter(hz.sup, forward_fn=fwd)
    status, raw, _ = router.route_predict(b"{}")
    assert status == 200 and raw == b'{"ok":1}'
    assert len(calls) == 2 and calls[0] != calls[1]  # alternate replica
    c = hz.sup.counters
    assert c["transport_error"] == 1 and c["retry"] == 1
    assert c["retry_ok"] == 1
    # the failed replica was unrouted pending the next probe
    assert not hz.sup.handles[calls[0]].routable


def test_router_retries_shed_503_on_alternate():
    hz = Harness(n=2)
    hz.up()
    calls = []

    def fwd(h, method, path, body, timeout):
        calls.append(h.index)
        if len(calls) == 1:
            return 503, b'{"error":"draining"}', "application/json"
        return 200, b'{"ok":1}', "application/json"

    router = ReplicaRouter(hz.sup, forward_fn=fwd)
    status, _, _ = router.route_predict(b"{}")
    assert status == 200
    assert calls[0] != calls[1]
    assert hz.sup.counters["transport_error"] == 0  # shed, not a crash


def test_router_retry_budget_exhaustion_sheds():
    hz = Harness(n=2)
    hz.up()
    hz.sup.retry_bucket = sv.TokenBucket(0, 0.0)  # budget already spent

    def fwd(h, method, path, body, timeout):
        raise ConnectionRefusedError("dead")

    router = ReplicaRouter(hz.sup, forward_fn=fwd)
    status, raw, _ = router.route_predict(b"{}")
    assert status == 503 and b"retry budget" in raw
    assert hz.sup.counters["retry_budget_exhausted"] == 1
    assert hz.sup.counters["retry"] == 0


def test_router_both_replicas_fail_502():
    hz = Harness(n=2)
    hz.up()

    def fwd(h, method, path, body, timeout):
        raise ConnectionRefusedError("dead")

    router = ReplicaRouter(hz.sup, forward_fn=fwd)
    status, raw, _ = router.route_predict(b"{}")
    assert status == 502 and b"both replicas failed" in raw
    assert hz.sup.counters["transport_error"] == 2


def test_router_lone_replica_own_503_stands():
    hz = Harness(n=1)
    hz.up()
    router = ReplicaRouter(
        hz.sup,
        forward_fn=lambda *a: (503, b'{"error":"queue full"}',
                               "application/json"))
    status, raw, _ = router.route_predict(b"{}")
    assert status == 503 and raw == b'{"error":"queue full"}'


# -- rolling hot reload -----------------------------------------------------


def test_rolling_reload_advances_generation_one_at_a_time():
    hz = Harness(n=2)
    hz.up()
    assert hz.sup.reload_to(dict(TARGET))
    assert hz.sup.generation == 1
    assert [h.generation for h in hz.sup.handles] == [1, 1]
    assert [i for i, _ in hz.reloads] == [0, 1]        # one at a time
    assert all(t["generation"] == 1 for _, t in hz.reloads)
    assert hz.sup.counters["reload"] == 2
    assert hz.sup.ready_count() == 2                   # all re-routed
    assert hz.sup.reload_to(dict(TARGET, epoch=4))
    assert hz.sup.generation == 2                      # monotonic


def test_rolling_reload_rejection_rolls_back_swapped():
    hz = Harness(n=2)
    hz.up()
    assert hz.sup.reload_to(dict(TARGET))              # generation 1 live
    hz.reloads.clear()
    hz.reload_status = (
        lambda h, t: 409 if (h.index == 1 and t["epoch"] == 4) else 200)
    assert not hz.sup.reload_to(dict(TARGET, epoch=4))
    assert hz.sup.generation == 1                      # NOT advanced
    assert hz.sup.counters["reload_rollback"] == 1
    # replica 0 (already swapped) was rolled back to the prior target
    back_index, back_target = hz.reloads[-1]
    assert back_index == 0
    assert back_target["epoch"] == 3 and back_target["generation"] == 1
    assert [h.generation for h in hz.sup.handles] == [1, 1]
    assert hz.sup.ready_count() == 2                   # plane still serves


def test_crash_mid_roll_skips_victim_then_catches_up():
    hz = Harness(n=2, backoff_base_s=0.5)
    hz.up()

    def die_during_first_swap(h, target):
        if h.index == 0 and not hz.proc(1).poll():
            hz.proc(1).die(9)
            hz.sup.poll(now=10.0)  # monitor notices mid-roll
        return 200

    hz.reload_status = die_during_first_swap
    assert hz.sup.reload_to(dict(TARGET))
    assert hz.sup.generation == 1
    assert [i for i, _ in hz.reloads] == [0]  # dead replica skipped
    h1 = hz.sup.handles[1]
    assert h1.generation == 0                 # fresh boot = boot weights
    hz.sup.poll(now=h1.next_spawn_t)          # respawn
    hz.sup.poll(now=h1.next_spawn_t + 1.0)    # ready → catch-up reload
    assert h1.state == sv.READY
    assert hz.reloads[-1] == (1, dict(TARGET, generation=1))
    assert h1.generation == 1                 # plane is one generation


def test_respawned_replica_catches_up_to_plane_generation():
    hz = Harness(n=2, backoff_base_s=0.5)
    hz.up()
    assert hz.sup.reload_to(dict(TARGET))
    hz.reloads.clear()
    hz.proc(1).die(9)
    hz.sup.poll(now=5.0)
    h1 = hz.sup.handles[1]
    assert h1.state == sv.BACKOFF and h1.generation == 0
    hz.sup.poll(now=h1.next_spawn_t)          # respawn
    hz.sup.poll(now=h1.next_spawn_t + 1.0)    # ready → catch-up
    assert h1.state == sv.READY and h1.generation == 1
    assert hz.reloads and hz.reloads[-1][0] == 1
    assert hz.reloads[-1][1]["generation"] == 1


# -- replica-side: checkpoint discovery + watcher ---------------------------


def _committed_ckpt(path):
    """Fabricate a COMMITTED checkpoint dir: int-named with real
    payload, the post-atomic-rename shape scan_checkpoints selects."""
    path.mkdir()
    (path / "params.npz").write_bytes(b"x")
    return path


def test_scan_checkpoints_prefers_furthest_position(tmp_path):
    assert rp.scan_checkpoints(str(tmp_path / "missing")) is None
    assert rp.scan_checkpoints(str(tmp_path)) is None   # empty prefix
    _committed_ckpt(tmp_path / "1")
    _committed_ckpt(tmp_path / "2")
    # in-progress orbax tmp dirs never int-parse → invisible
    (tmp_path / "3.orbax-checkpoint-tmp-99").mkdir()
    t = rp.scan_checkpoints(str(tmp_path))
    assert (t["kind"], t["epoch"], t["consumed"]) == ("epoch", 2, 0)
    steps = tmp_path / "steps"
    steps.mkdir()
    _committed_ckpt(steps / str(2 * 10 ** 7 + 5))  # epoch 2, consumed 5
    t = rp.scan_checkpoints(str(tmp_path))
    assert (t["kind"], t["epoch"], t["consumed"]) == ("step", 2, 5)
    _committed_ckpt(tmp_path / "3")         # a finished epoch 3 beats it
    t = rp.scan_checkpoints(str(tmp_path))
    assert (t["kind"], t["epoch"], t["consumed"]) == ("epoch", 3, 0)


def test_checkpoint_watcher_dedup_badlist_and_no_backward():
    current = {"t": {"prefix": "p", "kind": "epoch",
                     "epoch": 1, "consumed": 0}}
    calls = []
    accept = {"v": True}

    def scan(prefix):
        return dict(current["t"])

    def reload_fn(target):
        calls.append(dict(target))
        return accept["v"]

    w = rp.CheckpointWatcher("p", reload_fn, scan_fn=scan)
    w.prime()                        # boot checkpoint = already served
    assert w.poll_once() is None and not calls
    current["t"] = dict(current["t"], epoch=2)
    _, ok = w.poll_once()
    assert ok and len(calls) == 1
    assert w.poll_once() is None and len(calls) == 1   # dedup
    accept["v"] = False
    current["t"] = dict(current["t"], epoch=3)
    _, ok = w.poll_once()
    assert not ok and len(calls) == 2
    # a rejected target is blacklisted, never retried (no flapping)
    assert w.poll_once() is None and len(calls) == 2
    accept["v"] = True
    current["t"] = dict(current["t"], epoch=4)          # newer save wins
    _, ok = w.poll_once()
    assert ok and len(calls) == 3
    current["t"] = dict(current["t"], epoch=2)          # stale listing
    assert w.poll_once() is None and len(calls) == 3    # never backward


# -- replica-side: chaos env + canary swap ----------------------------------


def test_replica_faults_env_parsing_and_composer():
    env = {}
    env.update(replica_fault_env(0, kill_after=5))
    env.update(replica_fault_env(1, hang_after=3, slow_start_s=2.5))
    env.update(replica_fault_env(2, corrupt_ckpt=True))
    f0 = rp.ReplicaFaults(0, env=env)
    assert f0.kill_after == 5 and f0.hang_after is None
    assert f0.slow_start_s == 0.0 and not f0.corrupt_ckpt
    f1 = rp.ReplicaFaults(1, env=env)
    assert f1.kill_after is None and f1.hang_after == 3
    assert f1.slow_start_s == 2.5
    f2 = rp.ReplicaFaults(2, env=env)
    assert f2.corrupt_ckpt and f2.kill_after is None
    # comma-joined multi-index tokens: each replica reads its own
    f = rp.ReplicaFaults(1, env={rp.ENV_KILL_AFTER: "0:9,1:4"})
    assert f.kill_after == 4
    # malformed tokens are ignored, never fatal
    f = rp.ReplicaFaults(0, env={rp.ENV_KILL_AFTER: "banana"})
    assert f.kill_after is None


def test_poison_params_nans_float_leaves_only():
    params = {"a": {"w": np.ones((2, 2), np.float32)},
              "idx": np.arange(3, dtype=np.int32), "n": 2}
    out = rp.poison_params(params)
    assert np.isnan(out["a"]["w"]).all()
    assert np.array_equal(out["idx"], params["idx"])    # ints untouched
    assert not np.isnan(params["a"]["w"]).any()         # input unharmed


def test_engine_readiness_drain_and_resume():
    engine = make_engine(tiny_cfg(), batch_size=4).start()
    try:
        assert not engine.is_ready()           # warmup hasn't finished
        doc = engine.readiness()
        assert doc["ready"] is False and doc["warmed"] is False
        engine.mark_ready()
        assert engine.is_ready() and engine.readiness()["ready"]
        futs = [engine.submit(raw_image(60, 100, 40)) for _ in range(4)]
        assert engine.drain(timeout=10.0)      # quiesces, doesn't drop
        doc = engine.readiness()
        assert doc["ready"] is False and doc["draining"] is True
        with pytest.raises(RejectedError):
            engine.submit(raw_image(60, 100, 40))   # draining sheds
        for f in futs:
            assert f.result(timeout=10.0) is not None  # drained = SERVED
        engine.resume()
        assert engine.is_ready()
        engine.submit(raw_image(60, 100, 40))
    finally:
        engine.stop()


def _live_engine(batch_size=2):
    cfg = tiny_cfg()
    pred = FakeServePredictor(cfg, {"scale": np.float32(1.0)})
    engine = ServeEngine(pred, cfg, ServeOptions(
        batch_size=batch_size, max_delay_ms=1.0, max_queue=8)).start()
    warmup(engine)
    return engine, pred, cfg


def test_reload_engine_params_swap_is_zero_recompile():
    engine, pred, cfg = _live_engine()
    try:
        base = engine.submit(raw_image(96, 128, 40)).result(timeout=30.0)
        ok, info = rp.reload_engine_params(
            engine, pred, cfg, dict(TARGET),
            load_params_fn=lambda t, c: {"scale": np.float32(2.0)})
        assert ok and engine.generation == 1
        assert info["recompiles_during_swap"] == 0     # PR-7 registry reuse
        assert float(pred.params["scale"]) == 2.0
        assert engine.is_ready()                       # resumed after swap
        # the new weights actually serve: same image, scores doubled
        dets = engine.submit(raw_image(96, 128, 40)).result(timeout=30.0)
        assert base and dets
        assert dets[0]["score"] == pytest.approx(2.0 * base[0]["score"],
                                                 rel=1e-5)
    finally:
        engine.stop()


def test_reload_canary_rejects_nan_weights_and_rolls_back():
    engine, pred, cfg = _live_engine()
    try:
        good = pred.params
        ok, info = rp.reload_engine_params(
            engine, pred, cfg, dict(TARGET),
            load_params_fn=lambda t, c: {"scale": np.float32("nan")})
        assert not ok and info["rolled_back"]
        assert "canary" in info["error"]
        assert engine.generation == 0                  # never advanced
        assert pred.params is good                     # exact old leaves
        assert engine.is_ready()                       # still serving
        engine.submit(raw_image(96, 128, 40)).result(timeout=30.0)
    finally:
        engine.stop()


def test_reload_corrupt_ckpt_fault_forces_rollback():
    engine, pred, cfg = _live_engine()
    try:
        faults = rp.ReplicaFaults(0, env={rp.ENV_CORRUPT_CKPT: "0"})
        assert faults.corrupt_ckpt
        ok, info = rp.reload_engine_params(
            engine, pred, cfg, dict(TARGET),
            load_params_fn=lambda t, c: {"scale": np.float32(2.0)},
            faults=faults)
        assert not ok and info["rolled_back"]          # canary caught it
        assert float(pred.params["scale"]) == 1.0
        assert engine.generation == 0
    finally:
        engine.stop()


def test_make_reloader_validates_target():
    engine, pred, cfg = _live_engine()
    try:
        reloader = rp.make_reloader(
            engine, pred, cfg,
            load_params_fn=lambda t, c: {"scale": np.float32(2.0)})
        status, doc = reloader({"kind": "epoch"})      # missing keys
        assert status == 400 and "consumed" in doc["error"]
        status, doc = reloader(dict(TARGET))
        assert status == 200 and doc["generation"] == 1
        status, doc = reloader(dict(TARGET, generation=5))
        assert status == 200 and doc["generation"] == 5
        assert engine.generation == 5
    finally:
        engine.stop()


# -- end-to-end chaos: real supervisor over real subprocesses ---------------


def _e2e_opts():
    return sv.SupervisorOptions(
        probe_interval_s=0.2, probe_timeout_s=5.0, hang_probes=3,
        start_timeout_s=120.0, backoff_base_s=0.2, backoff_max_s=1.0,
        stable_s=5.0, drain_timeout_s=15.0, reload_timeout_s=60.0)


def _worker_spec(i, sock_dir, env=None, params_file=""):
    sock = os.path.join(sock_dir, f"r{i}.sock")
    argv = [sys.executable, WORKER, "--unix-socket", sock,
            "--replica-index", str(i)]
    if params_file:
        argv += ["--params-file", params_file]
    return sv.ReplicaSpec(argv=argv, sock=sock, index=i,
                          env={"JAX_PLATFORMS": "cpu", **(env or {})})


def _wait(cond, timeout=90.0, what=""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


def _predict_body():
    doc = encode_image_payload(np.full((60, 100, 3), 50, np.uint8))
    return json.dumps(doc).encode()


def test_e2e_kill9_failover_and_respawn(tmp_path):
    """Kill -9 one of two REAL replicas mid-burst: requests keep
    resolving (retry-once onto the survivor), the supervisor respawns
    the corpse, and the plane recovers to 2 ready."""
    specs = [_worker_spec(0, str(tmp_path),
                          env=replica_fault_env(0, kill_after=3)),
             _worker_spec(1, str(tmp_path))]
    # a LONG probe interval so the corpse stays routable until the next
    # monitor tick: with requests spaced well under it, some are
    # guaranteed to pick the dead replica and exercise the retry path
    # (0.2s probes can unroute the corpse before any request lands on
    # it — a race this test exists to close, not to rely on)
    opts = dataclasses.replace(_e2e_opts(), probe_interval_s=1.0)
    sup = sv.ReplicaSupervisor(specs, opts).start()
    try:
        _wait(lambda: sup.ready_count() == 2, what="both replicas ready")
        router = ReplicaRouter(sup)
        body = _predict_body()
        statuses = []
        for _ in range(30):
            status, _, _ = router.route_predict(body)
            statuses.append(status)
            time.sleep(0.02)
        # replica 0 SIGKILLed itself mid-burst (kill_after=3): every
        # request still resolved to a 2xx or an honest early shed — no
        # hangs, no hard 5xx escaping the retry
        assert set(statuses) <= {200, 503}, statuses
        assert statuses.count(200) >= 20, statuses
        assert sup.counters["transport_error"] >= 1
        assert sup.counters["retry_ok"] >= 1
        _wait(lambda: sup.counters["respawn"] >= 1, what="respawn")
        _wait(lambda: sup.ready_count() == 2, what="recovery to 2 ready")
    finally:
        sup.stop()


def test_e2e_rolling_reload_zero_dropped_requests(tmp_path):
    """Roll a hot reload through two REAL replicas under open traffic:
    every request lands a 2xx (drain sheds retry onto the other
    replica), the plane generation advances, zero recompiles."""
    pfile = str(tmp_path / "params.json")
    with open(pfile, "w") as f:
        json.dump({"scale": 1.0}, f)
    specs = [_worker_spec(i, str(tmp_path), params_file=pfile)
             for i in range(2)]
    sup = sv.ReplicaSupervisor(specs, _e2e_opts()).start()
    try:
        _wait(lambda: sup.ready_count() == 2, what="both replicas ready")
        router = ReplicaRouter(sup)
        body = _predict_body()
        statuses = []
        stop = threading.Event()

        def traffic():
            while not stop.is_set():
                status, _, _ = router.route_predict(body)
                statuses.append(status)
                time.sleep(0.03)

        th = threading.Thread(target=traffic, daemon=True)
        th.start()
        time.sleep(0.3)
        with open(pfile, "w") as f:
            json.dump({"scale": 2.0}, f)
        ok = sup.reload_to({"prefix": pfile, "kind": "file",
                            "epoch": 1, "consumed": 0})
        time.sleep(0.3)
        stop.set()
        th.join(timeout=30.0)
        assert ok and sup.generation == 1
        for h in sup.handles:
            assert h.generation == 1
        # THE zero-downtime claim: not one request dropped across the roll
        assert statuses and set(statuses) == {200}, statuses
        assert sup.counters["reload"] == 2
        assert sup.counters["reload_rollback"] == 0
    finally:
        sup.stop()
