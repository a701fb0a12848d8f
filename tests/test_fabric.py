"""Cross-host serving fabric tests (ISSUE 12).

Three layers, mirroring tests/test_replica.py:

* **Pool state machine** — deterministic unit tests with injected clock
  (``poll(now=...)``) and scripted ``probe_fn``/``reload_fn``: join,
  probe-failure eviction, backoff re-probe, quarantine + re-register,
  partition declare/heal, rolling reload with rollback and re-admission
  catch-up.
* **Router** — least-loaded over fresh queue_depth gauges with the
  stale-sample pin (a stale depth-0 member must NOT beat a fresh
  depth-5 one), retry-once under the token-bucket budget, per-member
  circuit breakers, and hedging counted apart from retries.
* **End-to-end chaos** — a REAL pool + router over REAL localhost-TCP
  subprocesses (``tests/fabric_worker.py``): kill -9 → eviction +
  retry keeps availability; ``MXR_FAULT_NET_RESET`` trips a breaker
  that closes after recovery; ``MXR_FAULT_NET_DROP`` partitions the
  majority away and the reachable subset keeps serving; a rolling
  remote reload lands with zero non-2xx.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.serve import fabric as fb
from mx_rcnn_tpu.serve import replica as rp
from mx_rcnn_tpu.serve import supervisor as sv
from mx_rcnn_tpu.serve import encode_image_payload, parse_address
from tests.faults import net_fault_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "fabric_worker.py")


@pytest.fixture(autouse=True)
def _restore_sink():
    yield
    telemetry.shutdown()


# -- addresses --------------------------------------------------------------


def test_parse_address_grammar():
    assert parse_address("127.0.0.1:8321") == ("tcp", "127.0.0.1", 8321)
    assert parse_address("hostA:80") == ("tcp", "hostA", 80)
    assert parse_address("/tmp/r0.sock") == ("unix", "/tmp/r0.sock", None)
    assert parse_address("unix:/tmp/r0.sock") == ("unix", "/tmp/r0.sock",
                                                  None)
    for bad in ("8321", "host:", ":80", "host:eighty"):
        with pytest.raises(ValueError):
            parse_address(bad)


def test_normalize_address_dedupes_spellings():
    assert fb.normalize_address(" 127.0.0.1:08321 ") == "127.0.0.1:8321"
    assert fb.normalize_address("/tmp/x.sock") == "unix:/tmp/x.sock"
    assert fb.normalize_address("unix:/tmp/x.sock") == "unix:/tmp/x.sock"
    with pytest.raises(ValueError):
        fb.normalize_address("nonsense")


# -- circuit breaker --------------------------------------------------------


def test_breaker_opens_half_opens_and_closes():
    br = fb.CircuitBreaker(threshold=3, cooldown_s=5.0)
    assert br.allow(now=0.0)
    assert not br.record_failure(now=0.0)
    assert not br.record_failure(now=0.0)
    assert br.record_failure(now=0.0)       # third failure OPENS (once)
    assert br.state == br.OPEN
    assert not br.allow(now=4.9)            # cooling down
    assert br.allow(now=5.0)                # the single half-open trial
    assert br.state == br.HALF_OPEN
    assert not br.allow(now=5.0)            # trial in flight: hold
    br.record_success()
    assert br.state == br.CLOSED and br.allow(now=5.1)


def test_breaker_half_open_failure_reopens():
    br = fb.CircuitBreaker(threshold=1, cooldown_s=2.0)
    assert br.record_failure(now=0.0)       # opens
    assert br.allow(now=2.0)                # trial
    assert br.record_failure(now=2.0)       # trial failed: re-opens
    assert br.state == br.OPEN
    assert not br.allow(now=3.9)
    assert br.allow(now=4.0)


# -- net fault parsing ------------------------------------------------------


def test_net_faults_parse_and_index_match():
    env = {rp.ENV_NET_DROP: "1:4", rp.ENV_NET_RESET: "0:2-5",
           rp.ENV_NET_DELAY: "2:150.5"}
    f0, f1, f2 = (rp.NetFaults(i, env) for i in range(3))
    assert f0.reset_from == 2 and f0.reset_to == 5
    assert f0.drop_after is None and f0.delay_ms == 0.0
    assert f1.drop_after == 4 and f1.reset_from is None
    assert f2.delay_ms == 150.5
    assert all(f.enabled for f in (f0, f1, f2))
    assert not rp.NetFaults(3, env).enabled
    # bare token = fault from the start; open-ended reset range
    f = rp.NetFaults(0, {rp.ENV_NET_DROP: "0", rp.ENV_NET_RESET: "0:3"})
    assert f.drop_after == 0
    assert f.reset_from == 3 and f.reset_to is None


def test_net_fault_env_composer_round_trips():
    env = {**net_fault_env(2, drop_after=3),
           **net_fault_env(1, delay_ms=25.0),
           **net_fault_env(0, reset_from=1, reset_to=6)}
    assert rp.NetFaults(2, env).drop_after == 3
    assert rp.NetFaults(1, env).delay_ms == 25.0
    f = rp.NetFaults(0, env)
    assert (f.reset_from, f.reset_to) == (1, 6)


def test_net_faults_reset_counts_only_predicts():
    class FakeConn:
        def setsockopt(self, *a):
            raise OSError("fake")

        def close(self):
            pass

    class FakeHandler:
        connection = FakeConn()
        close_connection = False

    f = rp.NetFaults(0, net_fault_env(0, reset_from=2))
    h = FakeHandler()
    assert not f.intercept("/readyz", h)      # probes never count
    assert not f.intercept("/predict", h)     # predict #1: before range
    assert not f.intercept("/healthz", h)
    assert f.intercept("/predict", h)         # predict #2: reset
    assert h.close_connection


# -- dormant-by-default: fork mode untouched --------------------------------


def test_build_child_argv_strips_fabric_flags():
    argv = ["serve.py", "--network", "resnet50", "--replicas", "2",
            "--fabric", "--join", "127.0.0.1:8320", "--pool-file", "/p",
            "--advertise", "h:1", "--hedge-after-ms", "50",
            "--partition-floor", "0.5", "--serve-batch", "4"]
    out = sv.build_child_argv(argv, "/tmp/r0.sock", 0)
    joined = " ".join(out)
    for flag in ("--fabric", "--join", "--pool-file", "--advertise",
                 "--hedge-after-ms", "--partition-floor"):
        assert flag not in joined, joined
    assert "--serve-batch 4" in joined
    assert out[-4:] == ["--unix-socket", "/tmp/r0.sock",
                        "--replica-index", "0"]


# -- pool state machine (scripted probes, fake clock) -----------------------


class PoolHarness:
    """A ReplicaPool with scriptable probe/reload answers per member."""

    def __init__(self, **opt_kw):
        self.answers = {}   # name -> (status, doc) | Exception
        self.probes = []    # member names in probe order
        self.reloads = []   # (name, target) in call order
        self.reload_status = 200

        def probe(member, path):
            self.probes.append(member.name)
            a = self.answers.get(member.name,
                                 OSError("connection refused"))
            if isinstance(a, Exception):
                raise a
            return a

        def reload_fn(member, target):
            self.reloads.append((member.name, dict(target)))
            st = (self.reload_status(member, target)
                  if callable(self.reload_status) else self.reload_status)
            if st == 200:
                return st, {"generation": target.get("generation"),
                            "recompiles_during_swap": 0}
            return st, {"error": "canary failed: injected"}

        self.pool = fb.ReplicaPool(fb.FabricOptions(**opt_kw),
                                   probe_fn=probe, reload_fn=reload_fn)

    def up(self, name, depth=0, generation=0):
        self.answers[name] = (200, {"ready": True, "queue_depth": depth,
                                    "generation": generation})

    def warming(self, name, depth=0):
        self.answers[name] = (503, {"ready": False, "queue_depth": depth})

    def down(self, name):
        self.answers[name] = OSError("connection refused")


A, B, C = "10.0.0.1:8000", "10.0.0.2:8000", "10.0.0.3:8000"


def test_register_probe_join():
    hz = PoolHarness()
    m, created = hz.pool.register(A, now=0.0)
    assert created and m.state == fb.JOINING and not m.routable
    _, created2 = hz.pool.register(A, now=0.0)
    assert not created2 and len(hz.pool.members) == 1
    hz.up(A, depth=3, generation=0)
    hz.pool.poll(now=1.0)
    assert m.state == fb.MEMBER_READY and m.routable
    assert m.depth == 3 and m.depth_t == 1.0
    assert hz.pool.counters["member_joined"] == 1


def test_warming_member_not_routable_not_evicted():
    hz = PoolHarness()
    m, _ = hz.pool.register(A, now=0.0)
    hz.warming(A)
    for t in (1.0, 2.0, 3.0, 4.0):
        hz.pool.poll(now=t)
    assert m.state == fb.JOINING and not m.routable  # alive, warming


def test_eviction_after_consecutive_probe_failures():
    hz = PoolHarness(evict_probes=3)
    m, _ = hz.pool.register(A, now=0.0)
    hz.up(A)
    hz.pool.poll(now=1.0)
    hz.down(A)
    hz.pool.poll(now=2.0)
    assert m.state == fb.MEMBER_READY and not m.routable  # suspect
    hz.pool.poll(now=3.0)
    assert m.state == fb.MEMBER_READY
    hz.pool.poll(now=4.0)                                 # third miss
    assert m.state == fb.EVICTED and m.depth_t is None
    assert hz.pool.counters["member_evicted"] == 1


def test_single_missed_probe_recovers_without_eviction():
    hz = PoolHarness(evict_probes=3)
    m, _ = hz.pool.register(A, now=0.0)
    hz.up(A)
    hz.pool.poll(now=1.0)
    hz.down(A)
    hz.pool.poll(now=2.0)
    assert not m.routable
    hz.up(A)
    hz.pool.poll(now=3.0)
    assert m.routable and m.probe_fails == 0
    assert hz.pool.counters["member_evicted"] == 0


def test_readmission_after_eviction_counts_as_join():
    hz = PoolHarness(evict_probes=1, backoff_base_s=0.5)
    m, _ = hz.pool.register(A, now=0.0)
    hz.up(A)
    hz.pool.poll(now=1.0)
    hz.down(A)
    hz.pool.poll(now=2.0)
    assert m.state == fb.EVICTED
    hz.up(A, generation=0)
    hz.pool.poll(now=2.1)            # backoff not elapsed: no probe yet
    assert m.state == fb.EVICTED
    hz.pool.poll(now=2.6)
    assert m.state == fb.MEMBER_READY and m.routable
    assert hz.pool.counters["member_joined"] == 2


def test_eviction_backoff_schedule_and_quarantine():
    hz = PoolHarness(evict_probes=1, backoff_base_s=0.5, backoff_max_s=4.0,
                     max_failures=100)
    m, _ = hz.pool.register(A, now=0.0)
    hz.up(A)
    hz.pool.poll(now=1.0)
    hz.down(A)
    now, delays = 1.0, []
    for _ in range(6):
        hz.pool.poll(now=now + 0.01)
        delays.append(round(m.next_probe_t - (now + 0.01), 3))
        now = m.next_probe_t
    assert delays == [0.5, 1.0, 2.0, 4.0, 4.0, 4.0]  # doubles, capped


def test_quarantine_stops_probing_until_reregister():
    hz = PoolHarness(evict_probes=1, backoff_base_s=0.1, max_failures=2)
    m, _ = hz.pool.register(A, now=0.0)
    hz.up(A)
    hz.pool.poll(now=1.0)
    hz.down(A)
    now = 1.0
    while m.state != fb.QUARANTINED:
        now = max(now + 0.2, m.next_probe_t)
        hz.pool.poll(now=now)
        assert now < 100.0
    assert hz.pool.counters["member_quarantined"] == 1
    n_probes = len(hz.probes)
    hz.pool.poll(now=now + 50.0)
    assert len(hz.probes) == n_probes    # quarantined: not probed
    # explicit re-register is the escape hatch
    _, created = hz.pool.register(A, now=now + 51.0)
    assert not created and m.state == fb.JOINING and m.failures == 0
    hz.up(A)
    hz.pool.poll(now=now + 52.0)
    assert m.state == fb.MEMBER_READY


def test_partition_declared_and_healed():
    hz = PoolHarness(evict_probes=1, partition_floor=0.5,
                     backoff_base_s=100.0)
    for name in (A, B, C):
        hz.pool.register(name, now=0.0)
        hz.up(name)
    hz.pool.poll(now=1.0)
    assert hz.pool.ready_count() == 3 and not hz.pool.partition
    hz.down(A)
    hz.down(B)
    hz.pool.poll(now=2.0)
    assert hz.pool.ready_count() == 1
    assert hz.pool.partition                   # 1/3 < 0.5
    assert hz.pool.counters["partition"] == 1
    hz.pool.poll(now=3.0)
    assert hz.pool.counters["partition"] == 1  # once per transition
    # heal: members answer again at their backoff instants
    hz.up(A)
    hz.up(B)
    for m in hz.pool.members.values():
        m.next_probe_t = 0.0
    hz.pool.poll(now=4.0)
    assert not hz.pool.partition and hz.pool.ready_count() == 3


def test_partition_alarm_gated_until_pool_ever_formed():
    hz = PoolHarness(partition_floor=0.5)
    hz.pool.register(A, now=0.0)
    hz.down(A)
    for t in (1.0, 2.0, 3.0):
        hz.pool.poll(now=t)
    assert not hz.pool.partition               # a boot, not a partition
    assert hz.pool.counters["partition"] == 0


def test_pool_file_seeds_members(tmp_path):
    pf = tmp_path / "pool.txt"
    pf.write_text(f"# fabric members\n{A}\n\n{B}  # rack 2\nunix:/tmp/x\n")
    hz = PoolHarness()
    assert hz.pool.load_pool_file(str(pf)) == 3
    assert set(hz.pool.members) == {A, B, "unix:/tmp/x"}


def test_rolling_reload_all_members_and_generation():
    hz = PoolHarness()
    for name in (A, B):
        hz.pool.register(name, now=0.0)
        hz.up(name)
    hz.pool.poll(now=1.0)
    assert hz.pool.reload_to({"prefix": "/ck", "kind": "file"})
    assert hz.pool.generation == 1
    assert [r[0] for r in hz.reloads] == [A, B]
    assert all(r[1]["generation"] == 1 for r in hz.reloads)
    m_a, m_b = hz.pool.members[A], hz.pool.members[B]
    assert m_a.generation == m_b.generation == 1
    assert m_a.routable and m_b.routable       # re-routed after the swap
    assert m_a.last_reload["recompiles_during_swap"] == 0
    assert hz.pool.counters["reload"] == 2
    assert hz.pool.counters["reload_rollback"] == 0


def test_rolling_reload_rejection_rolls_back_swapped_members():
    hz = PoolHarness()
    for name in (A, B):
        hz.pool.register(name, now=0.0)
        hz.up(name)
    hz.pool.poll(now=1.0)
    assert hz.pool.reload_to({"prefix": "/g1", "kind": "file"})
    hz.reloads.clear()
    # generation 2: B's canary rejects → A must roll BACK to gen 1
    hz.reload_status = lambda m, t: 409 if m.name == B else 200
    assert not hz.pool.reload_to({"prefix": "/g2", "kind": "file"})
    assert hz.pool.generation == 1             # monotonic, not advanced
    assert [(n, t["generation"], t["prefix"]) for n, t in hz.reloads] == \
        [(A, 2, "/g2"), (B, 2, "/g2"), (A, 1, "/g1")]
    assert hz.pool.counters["reload_rollback"] == 1
    assert hz.pool.members[A].generation == 1


def test_readmitted_member_catches_up_to_pool_generation():
    hz = PoolHarness(evict_probes=1, backoff_base_s=0.1)
    for name in (A, B):
        hz.pool.register(name, now=0.0)
        hz.up(name)
    hz.pool.poll(now=1.0)
    assert hz.pool.reload_to({"prefix": "/g1", "kind": "file"})
    hz.reloads.clear()
    hz.down(B)
    hz.pool.poll(now=2.0)
    assert hz.pool.members[B].state == fb.EVICTED
    # B restarts on its BOOT weights (generation 0) and is re-admitted:
    # the pool must catch it up to generation 1 before routing to it
    hz.up(B, generation=0)
    hz.pool.poll(now=3.0)
    assert hz.pool.members[B].state == fb.MEMBER_READY
    assert hz.reloads == [(B, dict({"prefix": "/g1", "kind": "file"},
                                   generation=1))]
    assert hz.pool.members[B].generation == 1


def test_reload_roll_survives_concurrent_register():
    """A /admin/register landing mid-roll (handler threads mutate the
    member dict while reload_to blocks inside _reload_one) must not
    abort the roll: the victim list and the post-roll catch-up loop
    both iterate a locked snapshot, never the live dict."""
    hz = PoolHarness()
    for name in (A, B):
        hz.pool.register(name, now=0.0)
        hz.up(name)
    hz.pool.poll(now=1.0)
    late_ready = []

    def reload_status(member, target):
        # every swap, a new member registers — the handler-thread race
        # run inline, so the dict mutates at the worst possible moment
        hz.pool.register(f"10.0.9.{len(hz.pool.members)}:8000", now=2.0)
        if member.name == A and not late_ready:
            # ... and one arrives READY at a stale generation, forcing
            # the catch-up loop itself to reload (and thus re-register)
            # mid-pass
            late, _ = hz.pool.register(C, now=2.0)
            late.state = fb.MEMBER_READY
            late.routable = True
            late_ready.append(late)
        return 200

    hz.reload_status = reload_status
    assert hz.pool.reload_to({"prefix": "/g1", "kind": "file"})
    assert hz.pool.generation == 1
    assert hz.pool.members[C].generation == 1  # straggler caught up


# -- router: least-loaded, the stale-gauge pin, retries, hedging ------------


def _ready_pool(depths, now=100.0, **opt_kw):
    """A pool with ready remote members at the given fresh depths."""
    hz = PoolHarness(**opt_kw)
    for name, depth in depths.items():
        m, _ = hz.pool.register(name, now=0.0)
        m.state = fb.MEMBER_READY
        m.routable = True
        if depth is not None:
            m.depth = depth
            m.depth_t = now
    return hz


def test_least_loaded_picks_min_depth_plus_inflight():
    hz = _ready_pool({A: 3, B: 1}, now=100.0)
    router = fb.FabricRouter(hz.pool)
    assert router._pick(now=100.1).name == B
    hz.pool.members[B].inflight = 5            # in-flight counts as load
    assert router._pick(now=100.1).name == A


def test_stale_gauge_ignored_by_least_loaded():
    """THE stale-gauge pin (ISSUE 12 satellite): a member whose depth-0
    sample is older than 2 probe intervals must NOT beat a member with a
    fresh depth-5 sample — a stale gauge is history, not load."""
    hz = _ready_pool({A: None, B: 5}, now=110.0, probe_interval_s=1.0,
                     stale_probe_intervals=2.0)
    m_a = hz.pool.members[A]
    m_a.depth = 0
    m_a.depth_t = 100.0                        # 10s old: stale
    router = fb.FabricRouter(hz.pool)
    for _ in range(4):                         # never the stale zero
        assert router._pick(now=110.5).name == B
    # metrics surface the same verdict the router acted on
    doc = hz.pool.metrics(now=110.5)
    assert doc["members"][A]["queue_depth_stale"]
    assert not doc["members"][B]["queue_depth_stale"]
    # ... and once EVERY sample is stale, round-robin over all routable
    hz.pool.members[B].depth_t = 100.0
    picked = {router._pick(now=110.5).name for _ in range(4)}
    assert picked == {A, B}


def test_depth_ties_rotate_round_robin():
    hz = _ready_pool({A: 0, B: 0}, now=100.0)
    router = fb.FabricRouter(hz.pool)
    picked = [router._pick(now=100.1).name for _ in range(4)]
    assert sorted(picked[:2]) == [A, B] and sorted(picked[2:]) == [A, B]


def test_open_breaker_excludes_member_from_picks():
    hz = _ready_pool({A: 0, B: 9}, now=100.0)
    hz.pool.members[A].breaker.state = fb.CircuitBreaker.OPEN
    hz.pool.members[A].breaker.open_until = 1e18
    router = fb.FabricRouter(hz.pool)
    assert router._pick(now=100.1).name == B


def test_unpicked_candidate_keeps_half_open_trial():
    """THE breaker-consumption pin: a cooled-down OPEN member that is a
    candidate but loses the least-loaded pick must KEEP its half-open
    trial — candidate filtering is can_attempt() (side-effect-free),
    and only the member actually picked pays allow().  Filtering with
    allow() burned the trial with no request behind it, leaving the
    member permanently unroutable after any transient failure burst."""
    hz = _ready_pool({A: 9, B: 0}, now=100.0)
    m_a = hz.pool.members[A]
    m_a.breaker.state = fb.CircuitBreaker.OPEN
    m_a.breaker.open_until = 99.0              # cooldown elapsed
    router = fb.FabricRouter(hz.pool)
    for _ in range(4):                         # B always wins on depth
        assert router._pick(now=100.1).name == B
    # A was a losing candidate 4 times over — its trial must survive
    assert m_a.breaker.state == fb.CircuitBreaker.OPEN
    assert m_a.breaker.can_attempt(100.2)
    # ... and the pick that finally lands on A consumes it for real
    hz.pool.members[B].routable = False
    assert router._pick(now=100.3).name == A
    assert m_a.breaker.state == fb.CircuitBreaker.HALF_OPEN
    m_a.breaker.record_success()
    assert m_a.breaker.state == fb.CircuitBreaker.CLOSED


def test_route_predict_retries_once_on_alternate():
    hz = _ready_pool({A: 0, B: 1}, now=time.monotonic())

    def forward(member, method, path, body, timeout):
        if member.name == A:
            raise ConnectionResetError("injected")
        return 200, b'{"ok": true}', "application/json"

    router = fb.FabricRouter(hz.pool, forward_fn=forward)
    status, raw, _ = router.route_predict(b"{}")
    assert status == 200 and b"ok" in raw
    c = hz.pool.counters
    assert c["transport_error"] == 1
    assert c["retry"] == 1 and c["retry_ok"] == 1
    assert c["hedge_fired"] == 0               # a retry is not a hedge
    assert not hz.pool.members[A].routable     # suspect until re-probed


def test_route_predict_retry_budget_exhausted_sheds():
    hz = _ready_pool({A: 0, B: 1}, now=time.monotonic(),
                     retry_budget=1, retry_refill_per_s=0.0)

    def forward(member, method, path, body, timeout):
        raise ConnectionResetError("injected")

    router = fb.FabricRouter(hz.pool, forward_fn=forward)
    # members become suspect as they fail; re-route them for each call
    status, _, _ = router.route_predict(b"{}")
    assert status in (502, 503)
    for m in hz.pool.members.values():
        m.routable = True
    status, _, _ = router.route_predict(b"{}")
    assert status == 503                       # budget gone: early shed
    assert hz.pool.counters["retry_budget_exhausted"] == 1


def test_route_predict_no_members_sheds():
    hz = PoolHarness()
    router = fb.FabricRouter(hz.pool)
    status, raw, ctype = router.route_predict(b"{}")
    assert status == 503 and ctype == "application/json"
    assert hz.pool.counters["no_ready"] == 1


def test_breaker_opens_after_consecutive_transport_failures():
    hz = _ready_pool({A: 0}, now=time.monotonic(), breaker_failures=2)

    def forward(member, method, path, body, timeout):
        raise ConnectionResetError("injected")

    router = fb.FabricRouter(hz.pool, forward_fn=forward)
    m = hz.pool.members[A]
    for _ in range(2):
        m.routable = True
        router.route_predict(b"{}")
    assert m.breaker.state == fb.CircuitBreaker.OPEN
    assert hz.pool.counters["breaker_open"] == 1
    m.routable = True
    status, _, _ = router.route_predict(b"{}")  # breaker holds the door
    assert status == 503
    assert hz.pool.counters["no_ready"] == 1


def test_member_503_is_breaker_neutral():
    hz = _ready_pool({A: 0}, now=time.monotonic(), breaker_failures=1)

    def forward(member, method, path, body, timeout):
        return 503, b'{"error": "shed"}', "application/json"

    router = fb.FabricRouter(hz.pool, forward_fn=forward)
    status, _, _ = router.route_predict(b"{}")
    assert status == 503                       # the lone member's own shed
    m = hz.pool.members[A]
    assert m.breaker.state == fb.CircuitBreaker.CLOSED


def test_hedge_fires_after_threshold_and_first_2xx_wins():
    now = time.monotonic()
    hz = _ready_pool({A: 0, B: 1}, now=now, hedge_after_ms=30.0)

    def forward(member, method, path, body, timeout):
        if member.name == A:
            time.sleep(0.4)                    # the slow primary
        return (200, json.dumps({"from": member.name}).encode(),
                "application/json")

    router = fb.FabricRouter(hz.pool, forward_fn=forward)
    t0 = time.monotonic()
    status, raw, _ = router.route_predict(b"{}")
    assert status == 200
    assert json.loads(raw)["from"] == B        # the hedge won
    assert time.monotonic() - t0 < 0.35        # did not wait out the slow
    c = hz.pool.counters
    assert c["hedge_fired"] == 1 and c["hedge_won"] == 1
    assert c["retry"] == 0                     # a hedge is not a retry


def test_fast_primary_never_hedges():
    hz = _ready_pool({A: 0, B: 1}, now=time.monotonic(),
                     hedge_after_ms=200.0)

    def forward(member, method, path, body, timeout):
        return 200, b'{"ok": 1}', "application/json"

    router = fb.FabricRouter(hz.pool, forward_fn=forward)
    status, _, _ = router.route_predict(b"{}")
    assert status == 200
    assert hz.pool.counters["hedge_fired"] == 0


def test_hedge_survives_primary_transport_death():
    now = time.monotonic()
    hz = _ready_pool({A: 0, B: 1}, now=now, hedge_after_ms=20.0)

    def forward(member, method, path, body, timeout):
        if member.name == A:
            time.sleep(0.1)
            raise ConnectionResetError("injected")
        return 200, b'{"ok": 1}', "application/json"

    router = fb.FabricRouter(hz.pool, forward_fn=forward)
    status, _, _ = router.route_predict(b"{}")
    assert status == 200
    assert hz.pool.counters["hedge_fired"] == 1


def test_pool_metrics_shape():
    hz = _ready_pool({A: 2}, now=100.0)
    doc = hz.pool.metrics(now=100.5)
    m = doc["members"][A]
    assert m["queue_depth"] == 2 and m["queue_depth_age_s"] == 0.5
    assert not m["queue_depth_stale"] and m["breaker"] == "closed"
    assert doc["ready"] == 1 and not doc["partition"]
    assert set(doc["counters"]) >= {"member_joined", "member_evicted",
                                    "breaker_open", "hedge_fired",
                                    "hedge_won", "partition"}


def test_fabric_prometheus_exposition():
    hz = _ready_pool({A: 2}, now=time.monotonic())
    hz.pool.count("hedge_fired")
    router = fb.FabricRouter(hz.pool)
    text = fb.fabric_prometheus(router)
    assert "fabric_hedge_fired" in text
    assert "fabric_ready_members" in text
    assert "fabric_partition_active" in text
    assert "fabric_queue_depth" in text


def test_fabric_prometheus_survives_evicted_member():
    """_evict clears depth_t but keeps depth; the Prometheus view must
    gate the age gauge on depth_t or /metrics?format=prom 500s whenever
    any member sits evicted awaiting re-probe."""
    hz = _ready_pool({A: 2, B: 1}, now=time.monotonic())
    hz.pool._evict(hz.pool.members[A], now=time.monotonic(),
                   reason="injected")
    text = fb.fabric_prometheus(fb.FabricRouter(hz.pool))  # must not raise
    # the evicted member's gauges drop; the survivor's still render
    assert "queue_depth_age_s_10_0_0_1:8000" not in text
    assert "queue_depth_age_s_10_0_0_2:8000" in text


def test_telemetry_report_fabric_health_section(tmp_path):
    from mx_rcnn_tpu.telemetry import report as trep
    tel = telemetry.configure(str(tmp_path), run_meta={"driver": "t"})
    tel.counter("fabric/member_evicted", 2)
    tel.counter("fabric/hedge_fired", 3)
    tel.counter("serve/requests", 5)
    telemetry.shutdown()
    summary = trep.aggregate(trep.load_events([str(tmp_path)]))
    table = trep.render_table(summary)
    assert "fabric health" in table
    idx = table.index("fabric health")
    block = table[idx:]
    assert "fabric/member_evicted" in block
    assert "fabric/breaker_open" in block      # zeros included
    assert "fabric/hedge_won" in block


# -- end-to-end chaos: real pool + router over real TCP subprocesses --------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _member_proc(port, index=0, env=None, params_file=""):
    argv = [sys.executable, WORKER, "--port", str(port),
            "--replica-index", str(index)]
    if params_file:
        argv += ["--params-file", params_file]
    return subprocess.Popen(
        argv, env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


def _e2e_opts(**kw):
    base = dict(probe_interval_s=0.2, probe_timeout_s=2.0,
                evict_probes=2, start_timeout_s=120.0,
                backoff_base_s=0.2, backoff_max_s=1.0, stable_s=5.0,
                drain_timeout_s=15.0, reload_timeout_s=60.0)
    base.update(kw)
    return fb.FabricOptions(**base)


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


def _cleanup(pool, procs):
    pool.stop()
    for p in procs:
        p.kill()
        p.wait(timeout=30)


def test_e2e_kill9_eviction_retry_and_readmission():
    """Kill -9 one of two REAL TCP members mid-burst: the router keeps
    availability over the survivor (retry-once), the pool EVICTS the
    corpse (no respawn authority over a remote host), and a restart on
    the same address is re-admitted by the probe loop alone."""
    ports = [_free_port(), _free_port()]
    procs = [_member_proc(ports[0], 0), _member_proc(ports[1], 1)]
    # a LONG probe interval keeps the corpse routable until the next
    # poll, guaranteeing requests land on it and exercise the retry
    # path (the same race test_replica's kill9 test closes)
    pool = fb.ReplicaPool(_e2e_opts(probe_interval_s=1.0))
    for port in ports:
        pool.register(f"127.0.0.1:{port}")
    pool.start()
    try:
        _wait(lambda: pool.ready_count() == 2, what="both members ready")
        router = fb.FabricRouter(pool, timeout_s=30.0)
        body = _predict_body()
        statuses = []
        for i in range(30):
            if i == 5:
                procs[0].kill()            # SIGKILL mid-burst
            status, _, _ = router.route_predict(body)
            statuses.append(status)
            time.sleep(0.02)
        # every response resolved to a 2xx or an honest shed — and the
        # availability floor holds over non-shed submits
        assert set(statuses) <= {200, 503}, statuses
        ok, shed = statuses.count(200), statuses.count(503)
        assert ok / max(len(statuses) - shed, 1) >= 0.9, statuses
        assert ok >= 20, statuses
        assert pool.counters["transport_error"] >= 1
        assert pool.counters["retry_ok"] >= 1
        _wait(lambda: pool.counters["member_evicted"] >= 1,
              what="eviction of the corpse")
        # restart on the SAME address: re-admission is the router's
        # re-probe loop, no re-register needed
        procs[0] = _member_proc(ports[0], 0)
        _wait(lambda: pool.ready_count() == 2, timeout=120.0,
              what="re-admission after restart")
        assert pool.counters["member_joined"] >= 3
    finally:
        _cleanup(pool, procs)


def test_e2e_net_reset_trips_breaker_then_closes():
    """``MXR_FAULT_NET_RESET`` on a member whose probes stay healthy:
    /predict connection resets must OPEN the per-member breaker (the
    readiness probe cannot see this failure mode), and once the reset
    range passes the half-open trial must CLOSE it again."""
    port = _free_port()
    procs = [_member_proc(port, 0,
                          env=net_fault_env(0, reset_from=1, reset_to=4))]
    pool = fb.ReplicaPool(_e2e_opts(breaker_failures=2,
                                    breaker_cooldown_s=0.5))
    pool.register(f"127.0.0.1:{port}")
    pool.start()
    try:
        _wait(lambda: pool.ready_count() == 1, what="member ready")
        m = pool.members[f"127.0.0.1:{port}"]
        router = fb.FabricRouter(pool, timeout_s=30.0)
        body = _predict_body()
        _wait(lambda: (router.route_predict(body),
                       pool.counters["breaker_open"] >= 1)[1],
              timeout=30.0, what="breaker to open on resets")
        assert pool.counters["transport_error"] >= 2
        # recovery: past the reset range a half-open trial lands a 200
        # and the breaker closes — the member is back in rotation
        def recovered():
            status, _, _ = router.route_predict(body)
            return (status == 200
                    and m.breaker.state == fb.CircuitBreaker.CLOSED)
        _wait(recovered, timeout=60.0, what="breaker to close again")
    finally:
        _cleanup(pool, procs)


def test_e2e_partition_flight_dump_and_degraded_serving(tmp_path):
    """``MXR_FAULT_NET_DROP`` blackholes 2 of 3 members (alive but
    unreachable — the partition shape): the pool evicts them off probe
    timeouts, declares ``fabric_partition`` (counter + flight dump),
    and the reachable subset KEEPS serving 200s."""
    telemetry.configure(str(tmp_path), run_meta={"driver": "t"})
    ports = [_free_port() for _ in range(3)]
    procs = [
        _member_proc(ports[0], 0, env=net_fault_env(0, drop_after=0)),
        _member_proc(ports[1], 1, env=net_fault_env(1, drop_after=0)),
        _member_proc(ports[2], 2),
    ]
    pool = fb.ReplicaPool(_e2e_opts(probe_timeout_s=0.5,
                                    partition_floor=0.5,
                                    backoff_max_s=0.5))
    for port in ports:
        pool.register(f"127.0.0.1:{port}")
    pool.start()
    try:
        _wait(lambda: pool.ready_count() == 3, what="all 3 ready")
        # a short forward timeout so requests that land on a member mid-
        # blackhole fail fast and retry instead of hanging the burst
        router = fb.FabricRouter(pool, timeout_s=2.0)
        body = _predict_body()
        # enough traffic that both faulted members cross their drop
        # threshold (first /predict each) and go dark
        for _ in range(8):
            router.route_predict(body)
            time.sleep(0.05)
        _wait(lambda: pool.partition, timeout=60.0,
              what="partition declared")
        assert pool.counters["partition"] >= 1
        assert pool.counters["member_evicted"] >= 2
        # the reachable subset serves: the survivor answers 200
        def survivor_200():
            status, _, _ = router.route_predict(body)
            return status == 200
        _wait(survivor_200, timeout=30.0, what="survivor serving 200s")
        flight = os.path.join(str(tmp_path), "flight_0.jsonl")
        assert os.path.exists(flight), "no flight dump"
        assert "fabric_partition" in open(flight).read()
    finally:
        _cleanup(pool, procs)
        telemetry.shutdown()


def test_e2e_rolling_remote_reload_zero_drops(tmp_path):
    """Roll a params swap across two REAL TCP members under open
    traffic: every request lands a 2xx, both members reach generation
    1, zero recompiles during either swap (registry-asserted via the
    reload response), no rollback."""
    pfile = str(tmp_path / "params.json")
    with open(pfile, "w") as f:
        json.dump({"scale": 1.0}, f)
    ports = [_free_port(), _free_port()]
    procs = [_member_proc(ports[i], i, params_file=pfile)
             for i in range(2)]
    pool = fb.ReplicaPool(_e2e_opts())
    for port in ports:
        pool.register(f"127.0.0.1:{port}")
    pool.start()
    try:
        _wait(lambda: pool.ready_count() == 2, what="both members ready")
        router = fb.FabricRouter(pool, timeout_s=30.0)
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
        ok = pool.reload_to({"prefix": pfile, "kind": "file",
                             "epoch": 1, "consumed": 0})
        time.sleep(0.3)
        stop.set()
        th.join(timeout=30.0)
        assert ok and pool.generation == 1
        for m in pool.members.values():
            assert m.generation == 1
            assert m.last_reload["recompiles_during_swap"] == 0
        # THE zero-downtime claim, now cross-host: not one dropped
        assert statuses and set(statuses) == {200}, statuses
        assert pool.counters["reload"] == 2
        assert pool.counters["reload_rollback"] == 0
    finally:
        _cleanup(pool, procs)


def test_e2e_join_self_registration():
    """A member started with ``--join`` registers itself: the router
    needs no prior knowledge of its address."""
    router_port = _free_port()
    member_port = _free_port()
    pool = fb.ReplicaPool(_e2e_opts())
    router = fb.FabricRouter(pool, timeout_s=30.0)
    server = fb.make_fabric_server(router, port=router_port)
    th = threading.Thread(target=server.serve_forever, daemon=True)
    th.start()
    pool.start()
    argv = [sys.executable, WORKER, "--port", str(member_port),
            "--join", f"127.0.0.1:{router_port}"]
    proc = subprocess.Popen(argv, env={**os.environ,
                                       "JAX_PLATFORMS": "cpu"})
    try:
        _wait(lambda: pool.ready_count() == 1, what="joined member ready")
        assert f"127.0.0.1:{member_port}" in pool.members
        # the router front door serves through the joined member
        from mx_rcnn_tpu.serve import tcp_http_request
        status, doc = tcp_http_request(
            "127.0.0.1", router_port, "GET", "/readyz", timeout=10.0)
        assert status == 200 and doc["ready_members"] == 1
        status, doc = tcp_http_request(
            "127.0.0.1", router_port, "POST", "/predict",
            json.loads(_predict_body()), timeout=30.0)
        assert status == 200 and "detections" in doc
    finally:
        server.shutdown()
        _cleanup(pool, [proc])
