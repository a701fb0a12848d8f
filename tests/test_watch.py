"""Watchtower tests (ISSUE 20).

Three layers, mirroring tests/test_autoscale.py:

* **Rule pack + history** — validation errors that name the offending
  rule, the shipped default pack, fingerprint stability, and the
  raw → 10s → 60s downsampling tiers (bounded memory, one stitched
  timeline, runaway-cardinality drop).
* **Lifecycle control loop** — deterministic fake-clock ``tick(now=)``
  tests over injected providers for all four rule kinds: threshold
  hold/fire/resolve (plus rate mode and the guard clause), burn-rate
  dual-window math against a real :class:`Hist` (and THE no-traffic
  pin: windowed quantiles never decay, so only the advance gate lets a
  burn alert resolve), absence arming (a series that never ran cannot
  fire its stall alert; parked fleet members are skipped), trend
  warmup, silences (mute the page, keep the record), and the
  ``alerts_<member>.jsonl`` / meta-event / flight-dump transition
  fan-out.
* **End-to-end** — a REAL router watchtower over REAL localhost-TCP
  members: killing one fires ``member_stale`` on the router with the
  tail-sampled trace ids attached, and a restart on the same address
  resolves it — the full arc persisted in ``alerts_router.jsonl``.

Plus the satellite pins: ``mxr_alert_state`` exposition format and
dormancy (watch off = fabric metrics, exposition and telemetry JSONL
byte-for-byte unchanged).
"""

import glob
import json
import os

import pytest

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.serve import fabric as fb
from mx_rcnn_tpu.telemetry import tracectx
from mx_rcnn_tpu.telemetry.sink import Hist
from mx_rcnn_tpu.telemetry.watch import (MetricHistory, RuleError,
                                         WatchOptions, Watchtower,
                                         alert_state_lines, default_rules,
                                         fingerprint, fleet_from_pool,
                                         load_rules, validate_rules)
from tests.test_fabric import (A, B, _cleanup, _e2e_opts, _free_port,
                               _member_proc, _predict_body,
                               _ready_pool, _wait)


@pytest.fixture(autouse=True)
def _restore_sink():
    yield
    telemetry.shutdown()
    tracectx.shutdown()


# -- options + rule pack ----------------------------------------------------


def test_watch_options_validation():
    with pytest.raises(ValueError):
        WatchOptions(interval_s=0.0)
    with pytest.raises(ValueError):
        WatchOptions(raw_keep=1)
    with pytest.raises(ValueError):
        WatchOptions(mid_step_s=60.0, coarse_step_s=10.0)
    with pytest.raises(ValueError):
        WatchOptions(max_series=0)


def _rule(**kw):
    base = {"name": "r", "kind": "threshold", "metric": "m",
            "op": ">", "value": 1.0}
    base.update(kw)
    return base


def test_rule_validation_errors_name_the_rule():
    cases = [
        ([{"kind": "threshold"}], "rule 0: missing required key 'name'"),
        ([_rule(kind="nope")], "rule 0 ('r')"),
        ([_rule(), _rule()], "rule 1 ('r'): duplicate"),
        ([_rule(bogus=1)], "unknown keys"),
        ([_rule(op="!=")], "op must be"),
        ([_rule(labels={"k": 1})], "labels must map strings"),
        ([_rule(scope="galaxy")], "scope must be"),
        ([_rule(kind="burn_rate", op=None, value=None, target_ms=100,
                fast_window_s=60, slow_window_s=30)],
         "slow_window_s must be >= fast_window_s"),
        ([_rule(guard={"metric": "g", "op": "=", "value": 0})],
         "guard.op"),
        ([_rule(for_s=-1)], "for_s must be >= 0"),
    ]
    for rules, needle in cases:
        rules = [{k: v for k, v in r.items() if v is not None}
                 for r in rules]
        with pytest.raises(RuleError) as ei:
            validate_rules(rules)
        assert needle in str(ei.value), (rules, str(ei.value))
    with pytest.raises(RuleError, match="version"):
        validate_rules({"version": 2, "rules": []})


def test_rule_defaults_filled_in():
    (r,) = validate_rules([{"name": "b", "kind": "burn_rate",
                            "metric": "m", "target_ms": 100}])
    assert r["quantile"] == 0.99 and r["budget"] == 0.05
    assert (r["fast_window_s"], r["slow_window_s"]) == (60.0, 300.0)
    assert (r["fast_burn"], r["slow_burn"]) == (6.0, 2.0)
    assert r["for_s"] == 0.0 and r["severity"] == "warning"
    assert r["scope"] == "local" and r["labels"] == {}


def test_default_pack_loads_and_names():
    names = {r["name"] for r in default_rules()}
    assert names == {"serve_p99_burn", "fabric_p99_burn", "shed_rate",
                     "steady_state_recompile", "member_stale",
                     "parked_fleet_under_load",
                     "flywheel_generation_stall"}


def test_load_rules_bad_file_names_the_path(tmp_path):
    p = tmp_path / "rules.json"
    p.write_text("{not json")
    with pytest.raises(RuleError, match="rules.json"):
        load_rules(str(p))
    with pytest.raises(RuleError, match="missing.json"):
        load_rules(str(tmp_path / "missing.json"))
    p.write_text(json.dumps([_rule(op="!=")]))
    with pytest.raises(RuleError, match="rule 0 \\('r'\\)"):
        load_rules(str(p))


def test_fingerprint_stable_and_label_sensitive():
    fp = fingerprint("a", {"x": "1", "y": "2"})
    assert fp == fingerprint("a", {"y": "2", "x": "1"})
    assert fp != fingerprint("a", {"x": "2", "y": "2"})
    assert fp != fingerprint("b", {"x": "1", "y": "2"})


# -- metric history ---------------------------------------------------------


def test_history_tiers_bound_memory_and_stitch_one_timeline():
    opts = WatchOptions(raw_keep=16, mid_keep=8, coarse_keep=8,
                        mid_step_s=10.0, coarse_step_s=60.0)
    h = MetricHistory(opts)
    for t in range(1200):                      # 20 min at 1 Hz
        h.record("m", float(t), float(t))
    pts = h.series("m", 1200.0, 1199.0)
    ts = [t for t, _ in pts]
    # one merged timeline: strictly increasing, no tier overlap, and
    # bounded far below the 1200 samples recorded
    assert ts == sorted(ts) and len(ts) == len(set(ts))
    assert len(pts) <= 16 + 8 + 8 + 2
    assert pts[-1] == (1199.0, 1199.0)         # newest raw point intact
    # the trailing window filter trims the coarse tail
    short = h.series("m", 100.0, 1199.0)
    assert all(t >= 1099.0 for t, _ in short) and short[-1][0] == 1199.0


def test_history_max_series_cap_drops_and_counts():
    h = MetricHistory(WatchOptions(max_series=2))
    for name in ("a", "b", "c", "c"):
        h.record(name, 1.0, 0.0)
    assert h.names() == ["a", "b"]
    assert h.stats() == {"series": 2, "dropped": 2}


def test_history_to_doc_stats():
    h = MetricHistory()
    for t, v in enumerate((3.0, 9.0, 1.0)):
        h.record("q", v, float(t))
    doc = h.to_doc("q", 60.0, 3.0)
    assert doc["metric"] == "q" and len(doc["points"]) == 3
    assert (doc["last"], doc["min"], doc["max"]) == (1.0, 1.0, 9.0)
    assert abs(doc["mean"] - 13.0 / 3) < 1e-9
    assert "last" not in h.to_doc("missing", 60.0, 3.0)


def test_last_change_age_arms_only_after_a_change():
    h = MetricHistory()
    for t in range(5):
        h.record("g", 7.0, float(t))
    age, changed = h.last_change_age("g", 10.0)
    assert not changed                         # constant series: unarmed
    h.record("g", 8.0, 5.0)
    age, changed = h.last_change_age("g", 11.0)
    assert changed and age == 6.0


# -- threshold lifecycle ----------------------------------------------------


class _Feed:
    """Scriptable summary provider: set gauges/counters per tick."""

    def __init__(self):
        self.counters = {}
        self.gauges = {}

    def summary(self):
        return {"counters": dict(self.counters),
                "gauges": {k: {"last": v}
                           for k, v in self.gauges.items()}}


def test_threshold_hold_fire_resolve_and_jsonl(tmp_path):
    feed = _Feed()
    rule = _rule(name="hot", for_s=2, severity="page")
    wt = Watchtower(rules=[rule], member="t", out_dir=str(tmp_path),
                    summary_fn=feed.summary)
    feed.gauges["m"] = 5.0
    recs = wt.tick(now=0.0)
    assert [r["state"] for r in recs] == ["pending"]
    assert wt.tick(now=1.0) == []              # hold not yet satisfied
    recs = wt.tick(now=2.0)
    assert [r["state"] for r in recs] == ["firing"]
    assert recs[0]["held_s"] == 2.0 and recs[0]["severity"] == "page"
    assert [i["alert"] for i in wt.firing(now=2.0)] == ["hot"]
    feed.gauges["m"] = 0.0
    recs = wt.tick(now=3.0)
    assert [r["state"] for r in recs] == ["resolved"]
    assert recs[0]["firing_s"] == 1.0
    assert wt.firing(now=3.0) == []
    # refire dedups onto the same fingerprint
    feed.gauges["m"] = 5.0
    fp2 = wt.tick(now=4.0)[0]["fingerprint"]
    assert fp2 == recs[0]["fingerprint"]
    # the atomic transition log holds the full arc
    path = tmp_path / "alerts_t.jsonl"
    logged = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r["state"] for r in logged] == ["pending", "firing",
                                           "resolved", "pending"]
    assert all(r["kind"] == "alert" and r["member"] == "t"
               and r["alert"] == "hot" for r in logged)
    doc = wt.alerts_doc(now=4.0)
    assert [r["alert"] for r in doc["resolved"]] == ["hot"]
    assert doc["counters"]["fired"] == 1
    assert doc["counters"]["resolved"] == 1


def test_threshold_pending_that_clears_is_not_an_incident():
    feed = _Feed()
    wt = Watchtower(rules=[_rule(name="blip", for_s=10)],
                    summary_fn=feed.summary)
    feed.gauges["m"] = 5.0
    assert [r["state"] for r in wt.tick(now=0.0)] == ["pending"]
    feed.gauges["m"] = 0.0
    assert wt.tick(now=1.0) == []              # no resolved record
    doc = wt.alerts_doc(now=1.0)
    assert doc["resolved"] == [] and doc["counters"]["fired"] == 0


def test_threshold_rate_mode_with_guard():
    feed = _Feed()
    rule = _rule(name="shedding", metric="c", mode="rate",
                 window_s=10.0, value=0.5,
                 guard={"metric": "g", "op": ">", "value": 0.0})
    wt = Watchtower(rules=[rule], summary_fn=feed.summary)
    feed.gauges["g"] = 0.0
    for t in range(6):                         # counter rises 1/s
        feed.counters["c"] = float(t)
        wt.tick(now=float(t))
    assert wt.firing(now=5.0) == []            # guard blocks the rate
    feed.gauges["g"] = 1.0
    feed.counters["c"] = 6.0
    recs = wt.tick(now=6.0)
    assert [r["state"] for r in recs] == ["pending", "firing"]
    assert recs[1]["value"] == pytest.approx(1.0)  # the measured rate


# -- burn rate --------------------------------------------------------------


def _burn_rule(**kw):
    base = {"name": "burn", "kind": "burn_rate", "metric": "lat",
            "quantile": 0.99, "target_ms": 100, "budget": 0.5,
            "fast_window_s": 5, "slow_window_s": 10,
            "fast_burn": 1.0, "slow_burn": 1.0}
    base.update(kw)
    return base


def test_burn_rate_fires_under_breach_and_resolves_when_traffic_stops():
    h = Hist()
    wt = Watchtower(rules=[_burn_rule()], hists_fn=lambda: {"lat": h})
    states = []
    for t in range(8):                         # sustained 1s >> 100ms
        h.observe(1.0, now=float(t))
        states += [r["state"] for r in wt.tick(now=float(t))]
    assert states[:2] == ["pending", "firing"]
    assert wt.firing(now=7.0)[0]["alert"] == "burn"
    # traffic stops: the hist never decays, but the advance gate zeroes
    # the violation bit and the window means drain the budget burn
    for t in range(8, 20):
        states += [r["state"] for r in wt.tick(now=float(t))]
    assert states[-1] == "resolved"
    assert wt.firing(now=19.0) == []


def test_burn_rate_no_traffic_burns_no_budget():
    h = Hist()
    rule = _burn_rule(fast_burn=2.0)           # needs an all-ones window
    wt = Watchtower(rules=[rule], hists_fn=lambda: {"lat": h})
    wt.tick(now=0.0)                           # empty hist: bit 0
    for _ in range(3):
        h.observe(10.0, now=0.5)               # one old terrible burst
    for t in range(1, 12):
        wt.tick(now=float(t))
    # the windowed quantile STILL reports the breach (hists don't
    # decay) — only the advance gate keeps the idle hist from burning
    assert h.window_quantile(0.99, 5.0, now=11.0) * 1000.0 > 100.0
    assert wt.history.value("alert/burn/violation") == 0.0
    assert wt.alerts_doc(now=11.0)["counters"]["fired"] == 0


def test_burn_rate_fleet_scope_labels_the_member():
    ha, hb = Hist(), Hist()

    def summaries():
        return {"rankA": {"hists": {"lat": ha.to_dict()}},
                "rankB": {"hists": {"lat": hb.to_dict()}}}

    wt = Watchtower(rules=[_burn_rule(scope="fleet")],
                    summaries_fn=summaries)
    for t in range(6):
        ha.observe(1.0, now=float(t))          # only rankA is burning
        wt.tick(now=float(t))
    firing = wt.firing(now=5.0)
    assert [i["labels"]["member"] for i in firing] == ["rankA"]
    assert firing[0]["labels"] != {} and len(firing) == 1


# -- absence ----------------------------------------------------------------


def test_absence_local_arms_only_after_first_change():
    feed = _Feed()
    rule = {"name": "stall", "kind": "absence", "metric": "gen",
            "value": 5}
    wt = Watchtower(rules=[rule], summary_fn=feed.summary)
    feed.gauges["gen"] = 1.0
    for t in range(20):                        # constant forever: quiet
        assert wt.tick(now=float(t)) == []
    feed.gauges["gen"] = 2.0                   # ran once → now armed
    wt.tick(now=20.0)
    for t in range(21, 26):
        assert wt.tick(now=float(t)) == []     # age <= 5 still fine
    recs = wt.tick(now=26.0)
    assert [r["state"] for r in recs] == ["pending", "firing"]
    feed.gauges["gen"] = 3.0                   # progress again
    assert [r["state"] for r in wt.tick(now=27.0)] == ["resolved"]


def _member(ready=True, parked=False, age=1.0):
    return {"state": "ready" if ready else "failed", "ready": ready,
            "parked": parked, "age_s": age, "queue_depth": 0.0,
            "inflight": 0.0, "generation": 0.0}


def test_absence_fleet_scope_stale_member_parked_skipped():
    members = {"m1": _member(), "m2": _member(),
               "m3": _member(ready=False, parked=True),
               "m4": _member(ready=False)}     # cold boot, never ready
    fleet = {"members": members, "fleet/members": 4.0, "fleet/ready": 2.0,
             "fleet/parked": 1.0, "fleet/demand": 0.0,
             "fleet/generation": 0.0}
    rule = {"name": "member_stale", "kind": "absence", "scope": "fleet",
            "metric": "member", "value": 15, "severity": "page"}
    wt = Watchtower(rules=[rule], fleet_fn=lambda: fleet)
    assert wt.tick(now=0.0) == []              # m2 arms (seen ready)
    members["m2"] = _member(ready=False, age=99.0)   # ...then goes dark
    wt.tick(now=1.0)
    firing = wt.firing(now=1.0)
    # m2 fires; parked m3 is intentionally idle and the never-yet-ready
    # m4 is a warm-up in progress — neither is a stale member
    assert [i["labels"]["member"] for i in firing] == ["m2"]
    members["m2"] = _member()                  # recovery
    recs = wt.tick(now=2.0)
    assert [r["state"] for r in recs] == ["resolved"]
    assert wt.firing(now=2.0) == []


# -- trend ------------------------------------------------------------------


def test_trend_warmup_gate_then_slope_fires_and_flattens_out():
    feed = _Feed()
    rule = {"name": "ramp", "kind": "trend", "metric": "c",
            "window_s": 10, "slope_gt": 0.5, "warmup_s": 5,
            "min_points": 3}
    wt = Watchtower(rules=[rule], summary_fn=feed.summary)
    for t in range(5):                         # rising 1/s, but warming
        feed.counters["c"] = float(t)
        assert wt.tick(now=float(t)) == []
    feed.counters["c"] = 5.0
    recs = wt.tick(now=5.0)                    # warm: slope 1.0 > 0.5
    assert [r["state"] for r in recs] == ["pending", "firing"]
    states = []
    for t in range(6, 20):                     # plateau: slope decays
        states += [r["state"] for r in wt.tick(now=float(t))]
    assert states == ["resolved"]


# -- silences ---------------------------------------------------------------


def test_silence_mutes_the_page_but_keeps_the_record(tmp_path):
    feed = _Feed()
    wt = Watchtower(rules=[_rule(name="noisy")], member="s",
                    out_dir=str(tmp_path), summary_fn=feed.summary)
    wt.silence("noisy", 50.0, now=0.0)
    feed.gauges["m"] = 5.0
    recs = wt.tick(now=0.0)
    # full lifecycle still runs and still logs, marked silenced
    assert [r["state"] for r in recs] == ["pending", "firing"]
    assert all(r["silenced"] for r in recs)
    assert wt.firing(now=0.0) == []
    doc = wt.alerts_doc(now=0.0)
    assert [i["alert"] for i in doc["silenced"]] == ["noisy"]
    assert doc["firing"] == []
    assert doc["silences"][0]["alertname"] == "noisy"
    assert doc["silences"][0]["expires_in_s"] == 50.0
    assert doc["counters"]["silenced"] == 1
    assert len(alert_state_lines(wt, now=0.0)) == 2  # header only
    logged = [json.loads(l)
              for l in (tmp_path / "alerts_s.jsonl").read_text()
              .splitlines()]
    assert all(r.get("silenced") for r in logged)
    # expiry: the still-active instance surfaces again, no re-fire
    assert wt.tick(now=60.0) == []
    assert [i["alert"] for i in wt.firing(now=60.0)] == ["noisy"]
    # a fresh silence can be lifted early
    sid = wt.silence("noisy", 100.0, now=60.0)
    assert wt.firing(now=61.0) == []
    assert wt.unsilence(sid) and not wt.unsilence(sid)
    assert [i["alert"] for i in wt.firing(now=61.0)] == ["noisy"]


# -- transition fan-out: meta events + flight dump --------------------------


def test_firing_fans_out_meta_event_and_flight_dump(tmp_path):
    telemetry.configure(str(tmp_path), run_meta={"driver": "t"})
    feed = _Feed()
    wt = Watchtower(rules=[_rule(name="hot")], member="rank0",
                    out_dir=str(tmp_path), summary_fn=feed.summary)
    feed.gauges["m"] = 5.0
    wt.tick(now=0.0)
    feed.gauges["m"] = 0.0
    wt.tick(now=1.0)
    telemetry.shutdown()
    events = [json.loads(l)
              for l in (tmp_path / "events_rank0.jsonl").read_text()
              .splitlines()]
    trans = [e for e in events if e.get("kind") == "meta"
             and e.get("name") == "alert_transition"]
    assert [e["fields"]["state"] for e in trans] == ["pending", "firing",
                                                    "resolved"]
    trigger = [e for e in events if e.get("kind") == "meta"
               and e.get("name") == "flight_trigger"]
    assert trigger and trigger[0]["fields"]["reason"] == "alert_firing"
    assert trigger[0]["fields"]["alert"] == "hot"
    assert "trace_ids" in trigger[0]["fields"]
    assert glob.glob(str(tmp_path / "flight_*.jsonl"))


# -- prometheus exposition --------------------------------------------------


def test_alert_state_lines_format():
    assert alert_state_lines(None) == []       # watch off: byte parity
    feed = _Feed()
    rules = [_rule(name="fast", severity="page", labels={"slo": "d"}),
             _rule(name="slow", metric="m2", for_s=100)]
    wt = Watchtower(rules=rules, member="r0", summary_fn=feed.summary)
    feed.gauges.update(m=5.0, m2=5.0)
    wt.tick(now=0.0)                           # fast fires, slow pends
    lines = alert_state_lines(wt, now=0.0)
    assert lines[0].startswith("# HELP mxr_alert_state ")
    assert lines[1] == "# TYPE mxr_alert_state gauge"
    samples = {l.rsplit(" ", 1)[0]: l.rsplit(" ", 1)[1]
               for l in lines[2:]}
    key = ('mxr_alert_state{alertname="fast",severity="page",'
           'member="r0",slo="d"}')
    assert samples[key] == "1"
    assert samples['mxr_alert_state{alertname="slow",'
                   'severity="warning",member="r0"}'] == "0.5"
    feed.gauges["m"] = 0.0
    wt.tick(now=1.0)                           # fast resolves → 0
    lines = alert_state_lines(wt, now=1.0)
    assert any(l == key + " 0" for l in lines)


# -- dormant by default: watch off = fabric unchanged -----------------------


def _echo_forward(member, method, path, body, timeout):
    return 200, b"{}", "application/json"


def test_watch_off_fabric_is_byte_inert(tmp_path):
    telemetry.configure(str(tmp_path), run_meta={"driver": "t"})
    hz = _ready_pool({A: 1, B: 2})
    router = fb.FabricRouter(hz.pool, forward_fn=_echo_forward)
    status, _, _ = router.route_predict(b"{}")
    assert status == 200
    # no watch pane, no route-latency hist, no alert family: the
    # watch-less fabric surfaces are exactly the PR-19 ones
    assert "watch" not in router.metrics()
    assert "fabric/route_time" not in telemetry.get().live_hists()
    assert "mxr_alert_state" not in fb.fabric_prometheus(router)
    summary = telemetry.get().summary()
    assert not any(k.startswith("watch/")
                   for k in (summary.get("counters") or {}))
    # attaching the watchtower opt-in grows all three
    router.watchtower = Watchtower(rules=[], member="router")
    status, _, _ = router.route_predict(b"{}")
    assert status == 200
    assert "fabric/route_time" in telemetry.get().live_hists()
    assert "watch" in router.metrics()
    assert "# TYPE mxr_alert_state gauge" in fb.fabric_prometheus(router)


def test_watchtower_constructed_but_never_ticked_is_dormant(tmp_path):
    telemetry.configure(str(tmp_path), run_meta={"driver": "t"})
    wt = Watchtower(rules=default_rules(), member="x")
    assert wt.history.stats() == {"series": 0, "dropped": 0}
    assert wt.state()["ticks"] == 0
    summary = telemetry.get().summary()
    assert not any(k.startswith("watch/")
                   for k in (summary.get("counters") or {}))
    assert not glob.glob(str(tmp_path / "alerts_*.jsonl"))


def test_history_doc_shape():
    feed = _Feed()
    wt = Watchtower(rules=[], summary_fn=feed.summary)
    feed.gauges["q"] = 2.0
    for t in range(5):
        wt.tick(now=float(t))
    doc = wt.history_doc("q", window_s=10.0, now=5.0)
    assert doc["metric"] == "q" and doc["window_s"] == 10.0
    assert doc["points"] and doc["last"] == 2.0
    assert wt.history_doc("nope", now=5.0)["points"] == []


def test_fleet_from_pool_normalizes_the_member_view():
    hz = _ready_pool({A: 3, B: 1}, now=100.0)
    doc = fleet_from_pool(hz.pool, now=100.0)
    assert doc["fleet/members"] == 2.0 and doc["fleet/ready"] == 2.0
    assert doc["fleet/parked"] == 0.0
    m = doc["members"][A]
    assert m["ready"] is True and m["queue_depth"] == 3.0


# -- end-to-end: kill a REAL member, the router watchtower pages ------------


def test_e2e_member_kill_fires_member_stale_with_traces_then_resolves(
        tmp_path):
    """Two REAL TCP members behind a router watchtower: SIGKILL one and
    ``member_stale`` must fire on the router labeled with that member
    and carrying >=1 tail-sampled trace id; restarting the member on
    the same address must resolve it — the full arc persisted in
    ``alerts_router.jsonl``."""
    ports = [_free_port(), _free_port()]
    procs = [_member_proc(ports[0], 0), _member_proc(ports[1], 1)]
    # evict_probes high: the corpse must stay IN the pool as a stale
    # member (the alert's subject) instead of being evicted out of it
    pool = fb.ReplicaPool(_e2e_opts(probe_interval_s=0.2,
                                    evict_probes=100000))
    for port in ports:
        pool.register(f"127.0.0.1:{port}")
    pool.start()
    # tail_quantile 0 keeps every completed route tree: the firing
    # alert must have forensics to attach
    tracectx.configure(str(tmp_path), member="router", sample=1.0,
                       tail_quantile=0.0)
    victim = f"127.0.0.1:{ports[0]}"
    try:
        _wait(lambda: pool.ready_count() == 2, what="both members ready")
        router = fb.FabricRouter(pool, timeout_s=30.0)
        rules = [{"name": "member_stale", "kind": "absence",
                  "scope": "fleet", "metric": "member", "value": 15,
                  "severity": "page"}]
        wt = Watchtower(rules=rules, member="router",
                        out_dir=str(tmp_path),
                        fleet_fn=lambda: fleet_from_pool(pool))
        router.watchtower = wt
        body = _predict_body()
        for _ in range(4):
            status, _, _ = router.route_predict(body)
            assert status == 200
        wt.tick()
        assert wt.firing() == []               # healthy fleet: quiet
        procs[0].kill()
        procs[0].wait(timeout=30)

        def fired():
            wt.tick()
            return any(i["alert"] == "member_stale"
                       for i in wt.firing())

        _wait(fired, timeout=60.0, what="member_stale firing")
        inst = [i for i in wt.firing()
                if i["alert"] == "member_stale"][0]
        assert inst["labels"]["member"] == victim
        assert len(inst["trace_ids"]) >= 1
        procs[0] = _member_proc(ports[0], 0)   # same address, reborn

        def resolved():
            wt.tick()
            return any(r["alert"] == "member_stale"
                       for r in wt.alerts_doc()["resolved"])

        _wait(resolved, timeout=150.0, what="member_stale resolved")
        assert not any(i["alert"] == "member_stale"
                       for i in wt.firing())
        logged = [json.loads(l)
                  for l in (tmp_path / "alerts_router.jsonl")
                  .read_text().splitlines()]
        arc = [r["state"] for r in logged
               if r["alert"] == "member_stale"
               and r["labels"].get("member") == victim]
        assert arc == ["pending", "firing", "resolved"]
        fire_rec = [r for r in logged if r["state"] == "firing"][0]
        assert len(fire_rec["trace_ids"]) >= 1
    finally:
        _cleanup(pool, procs)
