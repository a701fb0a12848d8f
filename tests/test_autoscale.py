"""Elastic autoscaling tests (ISSUE 18).

Three layers, mirroring tests/test_fabric.py:

* **Authority decision loop** — deterministic unit tests with injected
  clock (``tick(now=...)``) over a scripted pool: predictive scale-up on
  a rising trend (the forecast acts while current demand is still under
  target), capacity-source preference (parked member → standby address →
  supervisor fork), graceful scale-down through the drain, hysteresis
  dead band, consecutive-low-tick streaks, thrash freeze, and the
  zero-recompile verification with an injected compile probe.
* **Actuation surfaces** — supervisor on-demand ``add_replica`` /
  ``retire_replica`` over fake procs (slot templating, the
  ``build_child_argv`` tail contract, drain-then-reap), the pool's
  ``adopt_handle``/``release_local`` doors, and THE satellite-3 race:
  ``/admin/register`` landing mid-park-drain must end fully routable or
  fully parked, never half-routable.
* **End-to-end chaos** — a REAL pool over REAL localhost-TCP
  subprocesses: fleet drains to min when idle, a flash crowd unparks the
  spare, routing holds throughout, and the registry counters certify
  zero recompiles across the scale events.

Plus the satellite pins: Prometheus ``fabric_member_count{state=...}``
gauges and dormancy (autoscale off = the fabric byte-for-byte unchanged).
"""

import json
import threading
import time

import pytest

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.serve import autoscaler as ac
from mx_rcnn_tpu.serve import fabric as fb
from mx_rcnn_tpu.serve import supervisor as sv
from tests.test_fabric import (A, B, C, PoolHarness, _cleanup, _e2e_opts,
                               _free_port, _member_proc,
                               _predict_body, _ready_pool, _wait)


@pytest.fixture(autouse=True)
def _restore_sink():
    yield
    telemetry.shutdown()


def _opts(**kw):
    base = dict(min_members=1, max_members=4, target_depth=4.0,
                forecast_s=0.0, up_cooldown_s=0.0, down_cooldown_s=0.0,
                down_after_ticks=1)
    base.update(kw)
    return ac.AutoscalerOptions(**base)


def _depth(hz, name, depth, now):
    m = hz.pool.members[name]
    m.depth = depth
    m.depth_t = now


# -- options ----------------------------------------------------------------


def test_options_validation():
    ac.AutoscalerOptions()  # defaults are a legal configuration
    with pytest.raises(ValueError):
        ac.AutoscalerOptions(min_members=-1)
    with pytest.raises(ValueError):
        ac.AutoscalerOptions(min_members=3, max_members=2)
    with pytest.raises(ValueError):
        ac.AutoscalerOptions(target_depth=0.0)
    with pytest.raises(ValueError):
        ac.AutoscalerOptions(down_headroom=1.0)  # bands must not touch
    with pytest.raises(ValueError):
        ac.AutoscalerOptions(down_after_ticks=0)


# -- decision loop (fake clock, scripted pool) ------------------------------


def test_predictive_scale_up_on_rising_trend():
    """THE forecast pin: demand 2 is comfortably under target 4, but a
    +1/s slope through a 10s look-ahead forecasts 12 — the authority
    must scale BEFORE the queue is deep, because capacity takes seconds
    a flash crowd doesn't grant."""
    hz = _ready_pool({A: 1}, now=100.0)
    a = ac.CapacityAuthority(
        hz.pool, standby=[B], compile_probe=lambda: 0,
        opts=_opts(max_members=2, forecast_s=10.0))
    assert a.tick(now=100.0) == []          # flat: no trend yet
    _depth(hz, A, 2, 101.0)
    decisions = a.tick(now=101.0)
    assert [d["action"] for d in decisions] == ["scale_up:admit_standby"]
    assert decisions[0]["reason"] == "forecast_over_target"
    assert decisions[0]["demand"] == 2.0    # still under target — the
    assert decisions[0]["forecast"] == 12.0  # forecast did the scaling
    assert hz.pool.members[B].state == fb.JOINING
    assert a.counters["scale_up"] == 1 and a.counters["admit_standby"] == 1
    assert a.state()["pending_verify"] == 1  # recompile check armed


def test_scale_up_prefers_parked_member():
    """Capacity-source order: a parked member is a warm process that
    costs nothing to bring back — it must win over the standby list and
    the fork spawn."""
    hz = _ready_pool({A: 20, B: 0}, now=100.0)
    mb = hz.pool.members[B]
    mb.state = fb.PARKED
    mb.routable = False
    a = ac.CapacityAuthority(hz.pool, standby=[C],
                             compile_probe=lambda: 0, opts=_opts())
    decisions = a.tick(now=100.0)
    assert [d["action"] for d in decisions] == ["scale_up:unpark"]
    assert mb.state == fb.JOINING
    assert C not in hz.pool.members         # standby untouched
    assert a.counters["unpark"] == 1
    assert hz.pool.counters["member_unparked"] == 1


def test_scale_up_blocked_without_capacity_source():
    hz = _ready_pool({A: 20}, now=100.0)
    a = ac.CapacityAuthority(hz.pool, compile_probe=lambda: 0,
                             opts=_opts())
    decisions = a.tick(now=100.0)
    assert [d["action"] for d in decisions] == ["blocked"]
    assert a.counters["blocked"] == 1 and a.counters["scale_up"] == 0
    assert hz.pool.capacity_count() == 1    # nothing changed


def test_below_min_scales_up_regardless_of_demand():
    hz = PoolHarness()
    a = ac.CapacityAuthority(hz.pool, standby=[A],
                             compile_probe=lambda: 0,
                             opts=_opts(min_members=1))
    decisions = a.tick(now=0.0)             # zero demand, zero fleet
    assert decisions and decisions[0]["reason"] == "below_min"
    assert A in hz.pool.members


def test_shed_pressure_scales_up():
    """A shedding SLO controller is immediate pressure — no forecast
    needed, the engine is already refusing work."""
    class Shedding:
        def capacity_signal(self):
            return {"queue_depth": 0, "shedding": True}

    hz = _ready_pool({A: 0}, now=100.0)
    a = ac.CapacityAuthority(hz.pool, standby=[B],
                             controllers=[Shedding()],
                             compile_probe=lambda: 0, opts=_opts())
    decisions = a.tick(now=100.0)
    assert decisions and decisions[0]["reason"] == "shed_pressure"


def test_scale_down_parks_least_loaded_after_streak():
    hz = _ready_pool({A: 1, B: 0}, now=100.0)
    a = ac.CapacityAuthority(hz.pool, compile_probe=lambda: 0,
                             opts=_opts(down_after_ticks=3))
    for t in (100.0, 101.0):
        _depth(hz, A, 1, t)
        _depth(hz, B, 0, t)
        assert a.tick(now=t) == []          # streak still building
    _depth(hz, A, 1, 102.0)
    _depth(hz, B, 0, 102.0)
    decisions = a.tick(now=102.0)
    assert [d["action"] for d in decisions] == ["scale_down:park"]
    assert decisions[0]["member"] == B      # least (depth + inflight)
    mb = hz.pool.members[B]
    assert mb.state == fb.PARKED and not mb.routable
    assert mb.depth_t is None               # its gauge is history now
    assert hz.pool.ready_count() == 1
    assert a.counters["scale_down"] == 1 and a.counters["park"] == 1
    assert hz.pool.counters["member_parked"] == 1


def test_scale_down_never_below_min_members():
    hz = _ready_pool({A: 0}, now=100.0)
    a = ac.CapacityAuthority(hz.pool, compile_probe=lambda: 0,
                             opts=_opts(min_members=1))
    for t in (100.0, 101.0, 102.0, 103.0):
        _depth(hz, A, 0, t)
        assert a.tick(now=t) == []
    assert hz.pool.members[A].state == fb.MEMBER_READY


def test_hysteresis_holds_in_the_dead_band():
    """THE no-flap pin: demand oscillating between the down band
    (< 0.5×target per member) and the up threshold (> target) must
    produce zero scale actions — noise is not a trend."""
    hz = _ready_pool({A: 0, B: 0}, now=100.0)
    a = ac.CapacityAuthority(hz.pool, standby=[C],
                             compile_probe=lambda: 0, opts=_opts())
    for i in range(20):
        t = 100.0 + i
        _depth(hz, A, 5 if i % 2 == 0 else 7, t)  # per-member 2.5..3.5
        _depth(hz, B, 0, t)
        assert a.tick(now=t) == []
    assert a.counters["scale_up"] == 0 and a.counters["scale_down"] == 0
    assert a.counters["hold"] == 20


def test_down_streak_resets_when_load_returns():
    hz = _ready_pool({A: 0, B: 0}, now=100.0)
    a = ac.CapacityAuthority(hz.pool, compile_probe=lambda: 0,
                             opts=_opts(down_after_ticks=3))
    # the blip resets the streak AND holds the slope positive one more
    # tick — both gates have to re-earn the scale-down
    lows_then_blip = (0, 0, 6, 0, 0, 0)
    for i, d in enumerate(lows_then_blip):
        t = 100.0 + i
        _depth(hz, A, d, t)
        _depth(hz, B, 0, t)
        assert a.tick(now=t) == []          # streak never reaches 3
    assert a.counters["scale_down"] == 0
    _depth(hz, A, 0, 106.0)
    _depth(hz, B, 0, 106.0)
    decisions = a.tick(now=106.0)           # third consecutive low
    assert decisions and decisions[0]["action"] == "scale_down:park"


def test_up_cooldown_spaces_scale_ups():
    hz = _ready_pool({A: 20}, now=100.0)
    a = ac.CapacityAuthority(hz.pool, standby=[B, C],
                             compile_probe=lambda: 0,
                             opts=_opts(up_cooldown_s=5.0))
    assert a.tick(now=100.0)                # first up
    _depth(hz, A, 40, 101.0)
    assert a.tick(now=101.0) == []          # cooling down
    _depth(hz, A, 40, 105.0)
    assert a.tick(now=105.0)                # cooled: second up
    assert a.counters["scale_up"] == 2


def test_thrash_guard_freezes_and_flight_dumps(tmp_path):
    telemetry.configure(str(tmp_path), rank=0)
    hz = _ready_pool({A: 0}, now=100.0)
    a = ac.CapacityAuthority(
        hz.pool, standby=[B], compile_probe=lambda: 0,
        opts=_opts(thrash_flips=2, thrash_window_s=60.0, freeze_s=30.0))
    a._note_direction(100.0, +1)
    a._note_direction(101.0, -1)            # flip 1
    a._note_direction(102.0, +1)            # flip 2 → freeze
    assert a.counters["thrash_freeze"] == 1
    assert a._frozen_until == 132.0
    assert (tmp_path / "flight_0.jsonl").exists()
    # frozen: even hard over-target pressure holds
    _depth(hz, A, 50, 103.0)
    assert a.tick(now=103.0) == []
    # thawed: the same pressure acts again
    _depth(hz, A, 50, 140.0)
    decisions = a.tick(now=140.0)
    assert decisions and decisions[0]["action"].startswith("scale_up")


# -- zero-recompile invariant ----------------------------------------------


def test_zero_recompile_violation_detected(tmp_path):
    """A scale-up that causes the fleet's compiled-program count to grow
    broke the contract that new capacity warms from the shared AOT
    cache — counter + flight dump, not a silent regression."""
    telemetry.configure(str(tmp_path), rank=0)
    probes = iter([5, 8])                   # baseline, then verify: +3
    hz = _ready_pool({A: 20}, now=100.0)
    a = ac.CapacityAuthority(hz.pool, standby=[B],
                             compile_probe=lambda: next(probes),
                             opts=_opts(max_members=2))
    assert a.tick(now=100.0)                # scale up, baseline probed
    assert a.counters["recompile_check"] == 1
    hz.up(A, depth=20)
    hz.up(B)
    hz.pool.poll(now=100.5)                 # standby joins → ready
    assert hz.pool.ready_count() == 2
    a.tick(now=101.0)                       # check ripe → verify
    assert a.counters["recompile_violation"] == 3
    assert a.state()["pending_verify"] == 0
    flights = json.loads(
        (tmp_path / "flight_0.jsonl").read_text().splitlines()[-1])
    assert flights["fields"]["reason"] == "autoscale_recompile"


def test_zero_recompile_clean_scale_event():
    hz = _ready_pool({A: 20}, now=100.0)
    a = ac.CapacityAuthority(hz.pool, standby=[B],
                             compile_probe=lambda: 7,  # flat: no compiles
                             opts=_opts(max_members=2))
    assert a.tick(now=100.0)
    hz.up(A, depth=20)
    hz.up(B)
    hz.pool.poll(now=100.5)
    a.tick(now=101.0)
    assert a.counters["recompile_check"] == 1
    assert a.counters["recompile_violation"] == 0


def test_fleet_compiled_programs_sums_registry_misses():
    class M:
        def __init__(self, name, answer):
            self.name = name
            self.answer = answer

        def http(self, method, path, timeout=5.0):
            if isinstance(self.answer, Exception):
                raise self.answer
            return self.answer

    class P:
        def __init__(self, members):
            self._members = members

        def routable_members(self):
            return self._members

    pool = P([M(A, (200, {"compile": {"counters": {"aot_miss": 2,
                                                   "aot_hit": 9}}})),
              M(B, (200, {"counters": {}})),  # no registry: contributes 0
              M(C, (503, {})),                # warming: skipped
              M("10.0.0.9:8000", OSError("mid-death"))])  # unreachable
    assert ac.fleet_compile_counters(pool) == {A: 2}
    assert ac.fleet_compiled_programs(pool) == 2


def test_unpark_boot_history_is_not_a_recompile_violation():
    """The per-member baseline regression pin: a member that COMPILED at
    its own boot (cold cache) and was later parked must not trip the
    zero-recompile verify when it is unparked — its counter is history,
    not a scale-caused compile.  A fleet-wide sum gets this wrong: the
    unpark adds the member's old misses to the sum."""
    hz = _ready_pool({A: 20, B: 0}, now=100.0)
    hz.pool.park_member(B)
    # per-member probes as the default probe would see them: B carries 3
    # boot-time misses the whole way through; nobody compiles anything
    probes = iter([{A: 1, B: 3},       # baseline (B probed via extra)
                   {A: 1, B: 3}])      # verify: unchanged per member
    a = ac.CapacityAuthority(hz.pool, compile_probe=lambda: next(probes),
                             opts=_opts(max_members=2))
    decisions = a.tick(now=100.0)
    assert decisions[0]["action"] == "scale_up:unpark"
    hz.up(A, depth=20)
    hz.up(B)
    hz.pool.poll(now=100.5)
    a.tick(now=101.0)                  # check ripe → per-member diff
    assert a.counters["recompile_check"] == 1
    assert a.counters["recompile_violation"] == 0


def test_spawned_member_compiles_are_event_caused():
    """The flip side: a member absent from the baseline map (capacity
    this event created) owns every miss it reports — a spawn that
    compiles instead of warming from the shared cache is a violation."""
    hz = _ready_pool({A: 20}, now=100.0)
    probes = iter([{A: 1},             # baseline: fleet before the event
                   {A: 1, B: 2}])      # verify: the newcomer compiled
    a = ac.CapacityAuthority(hz.pool, standby=[B],
                             compile_probe=lambda: next(probes),
                             opts=_opts(max_members=2))
    assert a.tick(now=100.0)
    hz.up(A, depth=20)
    hz.up(B)
    hz.pool.poll(now=100.5)
    a.tick(now=101.0)
    assert a.counters["recompile_violation"] == 2


# -- actuation: supervisor on-demand capacity -------------------------------


class _FakeProc:
    def __init__(self):
        self.pid = 4242
        self.terminated = False
        self.killed = False

    def poll(self):
        return 0 if (self.terminated or self.killed) else None

    def terminate(self):
        self.terminated = True

    def wait(self, timeout=None):
        return 0

    def kill(self):
        self.killed = True


def _scale_sup(tmp_path, n=1):
    spawned = []

    def spawn(spec):
        p = _FakeProc()
        spawned.append(spec)
        return p

    specs = sv.replica_specs(["serve.py", "--serve-batch", "4"], n,
                             str(tmp_path))
    sup = sv.ReplicaSupervisor(specs, sv.SupervisorOptions(),
                               spawn_fn=spawn,
                               probe_fn=lambda h, p: (200, {}))
    return sup, spawned


def test_add_replica_templates_the_next_slot(tmp_path):
    sup, spawned = _scale_sup(tmp_path)
    sup.spawn_all(now=0.0)
    h = sup.add_replica(now=1.0)
    assert len(sup.handles) == 2 and h.index == 1
    assert h.spec.sock.endswith("replica_1.sock")
    # the build_child_argv tail contract held through templating
    assert h.spec.argv[-4:] == ["--unix-socket", h.spec.sock,
                                "--replica-index", "1"]
    assert "--serve-batch" in h.spec.argv    # serving flags inherited
    assert h.spec.env["MXR_REPLICA_INDEX"] == "1"
    assert spawned[-1] is h.spec             # spawned immediately
    assert sup.counters["scale_spawn"] == 1


def test_add_replica_on_empty_supervisor_needs_a_spec():
    sup = sv.ReplicaSupervisor([], sv.SupervisorOptions(),
                               spawn_fn=lambda s: _FakeProc(),
                               probe_fn=lambda h, p: (200, {}))
    with pytest.raises(RuntimeError, match="explicit spec"):
        sup.add_replica()


def test_retire_replica_drains_and_drops_the_slot(tmp_path):
    sup, _ = _scale_sup(tmp_path, n=2)
    sup.spawn_all(now=0.0)
    h = sup.handles[1]
    proc = h.proc
    assert sup.retire_replica(h)
    assert h not in sup.handles and len(sup.handles) == 1
    assert h.state == sv.STOPPED and not h.routable
    assert proc.terminated                  # graceful SIGTERM, not kill
    assert sup.counters["scale_retire"] == 1
    assert not sup.retire_replica(h)        # foreign/stale handle: False


def test_pool_adopts_and_releases_runtime_replicas(tmp_path):
    sup, _ = _scale_sup(tmp_path)
    sup.spawn_all(now=0.0)
    hz = PoolHarness()
    hz.pool.adopt_supervisor(sup)
    h = sup.add_replica(now=1.0)
    m = hz.pool.adopt_handle(h)
    assert m.name == "local/1" and m.name in hz.pool.members
    assert hz.pool.adopt_handle(h) is m     # idempotent
    assert hz.pool.release_local(m.name)
    assert m.name not in hz.pool.members
    assert not hz.pool.release_local(m.name)


# -- satellite 3: register racing a scale-down drain ------------------------


def test_register_mid_park_drain_defers_readmit():
    """THE half-routable pin: a register landing while the park drain is
    waiting out in-flight requests must not flip routing state mid-drain
    — the drain settles first, then the readmit wins and the member is
    FULLY back in rotation (ready + routable), never parked."""
    hz = _ready_pool({A: 0}, now=100.0)
    m = hz.pool.members[A]
    m.inflight = 1                          # the drain will block on this
    result = {}

    def park():
        result["parked"] = hz.pool.park_member(A)

    th = threading.Thread(target=park, daemon=True)
    th.start()
    _wait(lambda: m.scale_drain, timeout=10.0, what="drain to begin")
    assert not m.routable and m.reloading   # unrouted, drain in progress
    hz.pool.register(A, now=101.0)
    assert m.readmit_pending
    assert m.state == fb.MEMBER_READY       # register touched NO routing
    assert not m.routable                   # still drained-out
    m.inflight = 0                          # in-flight work completes
    th.join(timeout=10.0)
    assert not th.is_alive()
    assert result["parked"] is False        # the park was abandoned
    assert m.state == fb.MEMBER_READY and m.routable
    assert not m.reloading and not m.scale_drain and not m.readmit_pending
    assert hz.pool.counters["member_parked"] == 0


def test_register_after_park_is_a_clean_unpark():
    hz = _ready_pool({A: 0}, now=100.0)
    assert hz.pool.park_member(A)           # no in-flight: parks at once
    m = hz.pool.members[A]
    assert m.state == fb.PARKED and not m.routable
    assert hz.pool.counters["member_parked"] == 1
    hz.pool.register(A, now=101.0)
    assert m.state == fb.JOINING
    assert hz.pool.counters["member_unparked"] == 1
    hz.up(A)
    hz.pool.poll(now=101.5)                 # probe completes the rejoin
    assert m.state == fb.MEMBER_READY and m.routable


def test_parked_member_is_not_probed():
    hz = _ready_pool({A: 0}, now=100.0)
    assert hz.pool.park_member(A)
    hz.up(A)                                # a probe WOULD see it ready
    hz.pool.poll(now=105.0)
    assert hz.pool.members[A].state == fb.PARKED  # parked stays parked
    assert "10.0.0.1:8000" not in hz.probes


# -- satellite 1: Prometheus fleet-size gauges ------------------------------


def test_prometheus_member_count_by_state():
    hz = _ready_pool({A: 0, B: 0}, now=100.0)
    mb = hz.pool.members[B]
    mb.state = fb.PARKED
    mb.routable = False
    text = fb.fabric_prometheus(fb.FabricRouter(hz.pool))
    assert "# TYPE fabric_member_count gauge" in text
    assert 'fabric_member_count{state="ready"} 1' in text
    assert 'fabric_member_count{state="parked"} 1' in text
    # zeros are emitted, not omitted: absent-state asserts read 0
    assert 'fabric_member_count{state="evicted"} 0' in text
    assert text.endswith("\n")


def test_prometheus_autoscale_pane_when_enabled():
    hz = _ready_pool({A: 0}, now=100.0)
    router = fb.FabricRouter(hz.pool)
    text = fb.fabric_prometheus(router)
    assert "mxr_autoscale" not in text      # dormant: no series at all
    router.autoscaler = ac.CapacityAuthority(hz.pool,
                                             compile_probe=lambda: 0,
                                             opts=_opts())
    router.autoscaler.tick(now=100.0)
    text = fb.fabric_prometheus(router)
    assert "mxr_autoscale_demand" in text
    assert "mxr_autoscale_hold_total" in text


# -- dormant-by-default: autoscale off = fleet unchanged --------------------


def test_build_child_argv_strips_autoscale_flags():
    argv = ["serve.py", "--network", "resnet50", "--autoscale",
            "--autoscale-min", "1", "--autoscale-max", "4",
            "--autoscale-target-depth", "8",
            "--autoscale-interval-s", "0.5",
            "--autoscale-standby", "h:1,h:2", "--serve-batch", "4"]
    out = sv.build_child_argv(argv, "/tmp/r0.sock", 0)
    joined = " ".join(out)
    assert "--autoscale" not in joined      # children never self-scale
    assert "h:1,h:2" not in joined
    assert "--serve-batch 4" in joined


def test_autoscale_off_leaves_fabric_untouched():
    """The dormancy pin: without --autoscale no authority exists, the
    metrics pane has no autoscale key, and even CONSTRUCTING one (never
    started, never ticked) perturbs nothing in the pool."""
    hz = PoolHarness()
    hz.pool.register(A, now=0.0)
    hz.pool.register(B, now=0.0)
    router = fb.FabricRouter(hz.pool)
    assert router.autoscaler is None
    before = dict(hz.pool.counters)
    states = {n: m.state for n, m in hz.pool.members.items()}
    a = ac.CapacityAuthority(hz.pool, compile_probe=lambda: 0)
    assert hz.pool.counters == before
    assert {n: m.state for n, m in hz.pool.members.items()} == states
    assert a.ticks == 0
    doc = router.metrics()
    assert "autoscale" not in doc
    router.autoscaler = a
    assert "autoscale" in router.metrics()  # opt-in only


# -- end-to-end: real pool, real TCP members, member count tracks load ------


def test_e2e_fleet_tracks_load_with_zero_recompiles():
    """The ISSUE-18 chaos e2e over REAL localhost-TCP subprocesses: an
    idle two-member fleet drains to min (park through the in-flight
    drain), a flash crowd unparks the warm spare (scale-up through the
    register path), routing answers 2xx throughout, the load dropping
    drains it back down — and the registry counters certify the whole
    dance compiled NOTHING."""
    ports = [_free_port(), _free_port()]
    procs = [_member_proc(ports[0], 0), _member_proc(ports[1], 1)]
    pool = fb.ReplicaPool(_e2e_opts())
    for port in ports:
        pool.register(f"127.0.0.1:{port}")
    pool.start()
    try:
        _wait(lambda: pool.ready_count() == 2, what="both members ready")

        class Pressure:  # an injectable SLO-controller-shaped signal
            q = 0.0

            def capacity_signal(self):
                return {"queue_depth": self.q, "shedding": False}

        sig = Pressure()
        a = ac.CapacityAuthority(
            pool, controllers=[sig],
            opts=_opts(min_members=1, max_members=2, down_after_ticks=2,
                       thrash_flips=10))
        compiled_before = ac.fleet_compiled_programs(pool)

        # phase 1: idle → the authority drains the fleet back to min
        decisions = []
        for _ in range(4):
            decisions += a.tick()
            time.sleep(0.05)
        assert any(d["action"] == "scale_down:park" for d in decisions)
        assert pool.ready_count() == 1
        assert pool.member_state_counts().get(fb.PARKED) == 1
        router = fb.FabricRouter(pool, timeout_s=30.0)
        status, _, _ = router.route_predict(_predict_body())
        assert status == 200                # the shrunken fleet serves

        # phase 2: flash crowd → the warm spare is unparked
        sig.q = 50.0
        up = a.tick()
        assert any(d["action"] == "scale_up:unpark" for d in up)
        _wait(lambda: pool.ready_count() == 2,
              what="unparked member to rejoin")
        for _ in range(3):                  # let the verify checks close
            a.tick()
            time.sleep(0.05)
        assert a.counters["recompile_check"] >= 1
        assert a.counters["recompile_violation"] == 0
        assert ac.fleet_compiled_programs(pool) == compiled_before
        assert a.state()["pending_verify"] == 0
        status, _, _ = router.route_predict(_predict_body())
        assert status == 200

        # phase 3: the crowd passes → drain back down to min (the spike
        # still in the trend window holds the slope positive for a few
        # ticks — scale-down correctly waits it out)
        sig.q = 0.0
        down = []
        for _ in range(12):
            down += a.tick()
            if any(d["action"] == "scale_down:park" for d in down):
                break
            time.sleep(0.05)
        assert any(d["action"] == "scale_down:park" for d in down)
        assert pool.ready_count() == 1
        assert pool.member_state_counts().get(fb.PARKED) == 1
    finally:
        _cleanup(pool, procs)
