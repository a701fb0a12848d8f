"""Fleet flywheel (ISSUE 17): chaos-certified continuous learning at
fabric scale.

Four layers, mirroring the subsystem split:

* **Fleet capture** — member+pid shard naming (cross-host collision
  pin), atomic per-member manifests, and a merge that tolerates absent
  members, torn manifests, and duplicate deliveries.
* **Distributed mine** — per-member ranking passes folded into one
  global top-K: cross-member dedup, a fold order-independent down to
  the manifest BYTES (rid tie-break + canonical dedup winner), and the
  single-host ``flywheel.py mine`` path pinned byte-for-byte unchanged.
* **Gated promotion** — held-out eval shards (corrupt capture pixels
  skipped, torn shards fail the gate CLOSED), the measured-quality
  promotion gate accepting a good candidate and rolling a regressed one
  back without advancing the generation, and windowed
  score-distribution drift detection.
* **Chaos e2e** — 2 REAL TCP members sharing a capture dir under
  router traffic, then the full fleet loop with a partition mid-mine, a
  trainer SIGKILLed mid-epoch, one corrupt capture shard, and duplicate
  manifest delivery — it must still converge to a promoted generation
  on every member; a quality-regressed generation is rejected and no
  member ever serves it.
"""

import itertools
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.flywheel import (CaptureOptions, DriftDetector,
                                  FlywheelLoop, RequestCapture,
                                  build_eval_shard, detection_agreement,
                                  eval_shard_quality, fold_rankings,
                                  load_eval_shard, member_id,
                                  merge_manifests, mine_member,
                                  mine_shards, write_manifest)
from mx_rcnn_tpu.flywheel import capture as fcap
from mx_rcnn_tpu.flywheel import fleet as ffleet
from mx_rcnn_tpu.flywheel.fleet import FleetFlywheel, score_distribution
from mx_rcnn_tpu.serve import ServeEngine, ServeOptions
from mx_rcnn_tpu.serve import fabric as fb
from mx_rcnn_tpu.serve import replica as rp
from tests.faults import fleet_fault_env, flywheel_fault_env
from tests.replica_worker import FakeServePredictor, load_params
from tests.test_serve import raw_image
from tests.test_serve import tiny_cfg as serve_cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "fabric_worker.py")


@pytest.fixture(autouse=True)
def _restore_sink():
    yield
    telemetry.shutdown()


def synth_dets(rng, n, lo=0.35, hi=0.9):
    scores = np.sort(rng.uniform(lo, hi, n))[::-1]
    return [{"cls": 1, "score": float(s),
             "bbox": [4.0, 6.0, 60.0, 50.0]} for s in scores]


def fill_member_capture(capture_dir, member, seed=0, n=8,
                        shard_records=4, env=None):
    """Spill n records into a SHARED capture dir as one fleet member."""
    cap = RequestCapture(CaptureOptions(
        capture_dir=capture_dir, shard_records=shard_records,
        member=member), env=env or {})
    rng = np.random.RandomState(seed)
    for _ in range(n):
        px = rng.randint(0, 255, (64, 96, 3), dtype=np.uint8)
        cap.record_batch(
            [(px, (60, 90), (120, 180), synth_dets(rng, 4))], generation=1)
    cap.close()
    return cap


# -- fleet capture ---------------------------------------------------------


def test_shard_and_manifest_names_carry_member_and_pid(tmp_path):
    """Satellite 1: two members sharing one capture dir (same pid —
    the worst-case shared-pid-namespace view) never collide, because
    the member id sits in every shard and manifest name."""
    d = str(tmp_path)
    fill_member_capture(d, "m0", seed=0)
    fill_member_capture(d, "m1", seed=1)
    pid = os.getpid()
    shard_names = sorted(n for n in os.listdir(d)
                         if n.startswith("shard-") and n.endswith(".jsonl"))
    assert len(shard_names) == 4 and len(set(shard_names)) == 4
    for member in ("m0", "m1"):
        prefix = f"shard-{member}-{pid}-"
        assert sum(n.startswith(prefix) for n in shard_names) == 2
        assert os.path.exists(os.path.join(
            d, f"manifest-{member}-{pid}.json"))
    # the sanitizer keeps the name grammar unambiguous: no separators,
    # no dashes inside a member id
    assert member_id("host-1/evil name") == "host_1_evil_name"
    assert "-" not in member_id() and member_id() != ""


def test_member_manifest_atomic_and_lists_every_shard(tmp_path):
    d = str(tmp_path)
    cap = fill_member_capture(d, "m0", n=8, shard_records=4)
    docs = fcap.list_member_manifests(d)
    assert len(docs) == 1
    doc = docs[0]
    assert doc["schema"] == fcap.CAPTURE_MANIFEST_SCHEMA
    assert doc["member"] == "m0" and doc["pid"] == os.getpid()
    assert doc["seq"] == 2 and len(doc["shards"]) == 2
    for base in doc["shards"]:
        assert os.path.exists(os.path.join(d, base + ".jsonl"))
    assert doc["counters"]["captured"] == cap.counters["captured"] == 8
    assert not [n for n in os.listdir(d) if n.endswith(".tmp")]


def test_manifest_merge_tolerates_torn_absent_and_duplicate(tmp_path):
    """Merge tolerance: a torn manifest is skipped (member simply not
    published yet), an absent member just isn't merged, and the
    injected duplicate delivery folds to ONE member entry."""
    d = str(tmp_path)
    fill_member_capture(d, "m0", seed=0,
                        env=fleet_fault_env(dup_manifest="m0"))
    fill_member_capture(d, "m1", seed=1)
    # a torn third member's manifest: interrupted mid-write
    with open(os.path.join(d, "manifest-late-99.json"), "w") as fh:
        fh.write('{"schema": "mxr_capture_man')
    dup_names = [n for n in os.listdir(d) if n.endswith(".dup.json")]
    assert dup_names, "dup-manifest injection wrote nothing"
    merged = merge_manifests(d)
    members = sorted(doc["member"] for doc in merged["members"].values())
    assert members == ["m0", "m1"]
    assert merged["duplicates_dropped"] >= 1
    # absent/late member arriving later is merged next round
    fill_member_capture(d, "m2", seed=2)
    merged = merge_manifests(d)
    assert sorted(doc["member"] for doc in
                  merged["members"].values()) == ["m0", "m1", "m2"]


# -- distributed mine ------------------------------------------------------


def test_mine_member_scans_exactly_claimed_shards(tmp_path):
    d = str(tmp_path)
    fill_member_capture(d, "m0", seed=0, n=8)
    fill_member_capture(d, "m1", seed=1, n=8)
    doc = next(m for m in merge_manifests(d)["members"].values()
               if m["member"] == "m0")
    r = mine_member(d, doc, top_k=16, min_label_score=0.1)
    assert r["member"] == "m0" and r["scanned"] == 8
    assert all(e["member"] == "m0" for e in r["entries"])
    assert r["missing_shards"] == 0
    # a stale claim (rotated-out shard) costs coverage, never the mine
    doc2 = dict(doc, shards=doc["shards"] + ["shard-m0-0-000099"])
    r2 = mine_member(d, doc2, top_k=16, min_label_score=0.1)
    assert r2["missing_shards"] == 1 and r2["scanned"] == 8


def _entry(npz, key, rid, h, member):
    return {"npz": npz, "key": key, "rid": rid, "hardness": h,
            "member": member, "signals": {}, "generation": 1,
            "trace_id": None, "bucket": [64, 96], "raw_hw": [60, 90],
            "orig_hw": [120, 180], "detections": []}


def test_fold_dedup_and_rid_tiebreak_order_independent():
    """Cross-member dedup on (npz, key); equal-hardness ties break on
    rid then (npz, key); the dedup winner's member tag is canonical
    (smallest member id), never first-seen — fold order cannot leak
    into the result."""
    rA = {"member": "a", "scanned": 2, "skipped": 0, "entries": [
        _entry("a.npz", "r1", 0, 1.0, "a"),
        _entry("shared.npz", "rX", 7, 0.8, "a")]}
    rB = {"member": "b", "scanned": 2, "skipped": 0, "entries": [
        _entry("b.npz", "r1", 0, 1.0, "b"),
        _entry("shared.npz", "rX", 7, 0.8, "b")]}
    fwd, _, scanned, _ = fold_rankings([rA, rB], top_k=8)
    rev, _, _, _ = fold_rankings([rB, rA], top_k=8)
    assert fwd == rev and scanned == 4
    assert [e["npz"] for e in fwd] == ["a.npz", "b.npz", "shared.npz"]
    # the shared record ranked ONCE, tagged with the canonical member
    shared = [e for e in fwd if e["npz"] == "shared.npz"]
    assert len(shared) == 1 and shared[0]["member"] == "a"
    # rid asc breaks a pure hardness tie across members
    assert fwd[0]["rid"] == fwd[1]["rid"] == 0


def test_fold_determinism_byte_identical_manifest(tmp_path):
    """Satellite 3: folding the same per-member rankings in ANY member
    order lands on a byte-identical ``mined-<digest>.json``."""
    d = str(tmp_path / "cap")
    for i, m in enumerate(("ma", "mb", "mc")):
        fill_member_capture(d, m, seed=i, n=8)
    rankings = [mine_member(d, doc, top_k=8, min_label_score=0.1)
                for doc in merge_manifests(d)["members"].values()]
    blobs, names = set(), set()
    for i, perm in enumerate(itertools.permutations(rankings)):
        train, evals, scanned, _ = fold_rankings(
            list(perm), top_k=6, eval_every=3)
        out = str(tmp_path / f"out{i}")
        path = write_manifest(d, train, scanned, 6, out_dir=out,
                              min_label_score=0.1,
                              extra={"members": sorted(r["member"]
                                                       for r in perm),
                                     "eval_entries": evals})
        names.add(os.path.basename(path))
        with open(path, "rb") as fh:
            blobs.add(fh.read())
    assert len(names) == 1 and len(blobs) == 1


def test_write_manifest_extra_is_additive_only(tmp_path):
    d = str(tmp_path)
    fill_member_capture(d, "m0", n=4)
    entries, scanned, _ = mine_shards(d, top_k=4, min_label_score=0.1)
    path = write_manifest(d, entries, scanned, 4,
                          extra={"members": ["m0"], "eval_entries": []})
    with open(path) as fh:
        doc = json.load(fh)
    assert doc["members"] == ["m0"] and doc["eval_entries"] == []
    with pytest.raises(ValueError, match="shadows"):
        write_manifest(d, entries, scanned, 4, extra={"entries": []})


def test_single_host_mine_byte_for_byte_unchanged(tmp_path):
    """The acceptance pin: with fleet mode off, ``flywheel.py mine``
    produces the exact legacy manifest — same keys, no member tags, and
    the CLI and in-process paths land on identical bytes."""
    d = str(tmp_path / "cap")
    fill_member_capture(d, "solo", n=8)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "flywheel.py"), "mine",
         "--capture-dir", d, "--top-k", "4", "--min-label-score", "0.3"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    path = json.loads(out.stdout.strip().splitlines()[-1])["manifest"]
    with open(path, "rb") as fh:
        cli_bytes = fh.read()
    doc = json.loads(cli_bytes)
    assert set(doc) == {"schema", "version", "capture_dir", "top_k",
                        "total_scanned", "min_label_score", "entries"}
    assert doc["entries"] and all("member" not in e
                                  for e in doc["entries"])
    os.unlink(path)
    res = FlywheelLoop(d, top_k=4).run_round(0)
    assert res["manifest"] == path
    with open(path, "rb") as fh:
        assert fh.read() == cli_bytes


# -- checkpoint discovery under partial writes (satellite 2) ---------------


def test_scan_checkpoints_never_selects_half_written(tmp_path):
    """A trainer killed mid-save leaves an empty or tmp-only int dir;
    the watcher must never select it (never-rolls-backward holds under
    partial writes) and must pick it up once the save commits."""
    (tmp_path / "2").mkdir()                      # dir created, no payload
    assert rp.scan_checkpoints(str(tmp_path)) is None
    (tmp_path / "2" / "payload.tmp-77").write_bytes(b"")  # still staging
    assert rp.scan_checkpoints(str(tmp_path)) is None
    steps = tmp_path / "steps"
    steps.mkdir()
    (steps / str(10 ** 7 + 3)).mkdir()            # half-written step save
    assert rp.scan_checkpoints(str(tmp_path)) is None
    c = tmp_path / "1"
    c.mkdir()
    (c / "params.npz").write_bytes(b"x")
    t = rp.scan_checkpoints(str(tmp_path))
    assert (t["kind"], t["epoch"], t["consumed"]) == ("epoch", 1, 0)
    calls = []
    w = rp.CheckpointWatcher(str(tmp_path),
                             lambda tgt: calls.append(tgt) or True)
    w.prime()
    assert w.poll_once() is None and not calls    # husks never flap it
    (tmp_path / "2" / "weights.npz").write_bytes(b"y")  # save commits
    got = w.poll_once()
    assert got is not None and got[1]
    assert calls and calls[0]["epoch"] == 2


# -- eval shards + agreement + drift ---------------------------------------


def test_build_eval_shard_skips_corrupt_pixels(tmp_path):
    d = str(tmp_path)
    fill_member_capture(d, "m0", n=8, shard_records=4,
                        env=flywheel_fault_env(corrupt_shard=0))
    doc = next(iter(merge_manifests(d)["members"].values()))
    r = mine_member(d, doc, top_k=8, min_label_score=0.1)
    path, kept, skipped = build_eval_shard(d, r["entries"],
                                           str(tmp_path / "ev"))
    assert kept == 4 and skipped == 4             # shard 0's npz is garbage
    shard = load_eval_shard(path)
    assert len(shard["records"]) == kept
    for rec in shard["records"]:
        assert shard["pixels"][rec["key"]].dtype == np.uint8
        assert rec["labels"]
    # the gate fails CLOSED on anything torn
    bad = str(tmp_path / "torn.json")
    with open(bad, "w") as fh:
        fh.write('{"schema": "mxr_eval_shard", "records"')
    with pytest.raises(ValueError):
        load_eval_shard(bad)
    with open(bad, "w") as fh:
        json.dump({"schema": "something_else"}, fh)
    with pytest.raises(ValueError, match="mxr_eval_shard"):
        load_eval_shard(bad)


def test_detection_agreement_semantics():
    box = [0.0, 0.0, 16.0, 16.0]
    p = [{"cls": 1, "score": 0.8, "bbox": box}]
    g = [{"cls": 1, "score": 0.7, "bbox": box}]
    assert detection_agreement([], []) == 1.0     # nothing to disagree
    assert detection_agreement(p, []) == 0.0
    assert detection_agreement([], g) == 0.0
    assert detection_agreement(p, g) == 1.0
    wrong_cls = [{"cls": 2, "score": 0.7, "bbox": box}]
    assert detection_agreement(p, wrong_cls) == 0.0
    # a collapsed candidate's sub-floor scores count as NO predictions
    weak = [{"cls": 1, "score": 0.01, "bbox": box}]
    assert detection_agreement(weak, g) == 0.0
    shifted = [{"cls": 1, "score": 0.8,
                "bbox": [100.0, 100.0, 120.0, 120.0]}]
    assert detection_agreement(shifted, g) == 0.0  # IoU below threshold


def test_drift_detector_windowed_vs_snapshot():
    base = [{"mean_score": 0.7, "entropy": 0.2,
             "bands": {"0.3": 3, "0.5": 2, "0.7": 1}}] * 8
    dd = DriftDetector(threshold=0.2, window=8, min_observed=4)
    assert dd.check() == (False, 0.0)             # no snapshot yet
    dd.snapshot(base)
    for s in base:
        dd.observe(s)
    drifted, metric = dd.check()
    assert not drifted and metric < 0.01
    shifted = [{"mean_score": 0.2, "entropy": 0.8,
                "bands": {"0.3": 1, "0.5": 0, "0.7": 0}}] * 8
    for s in shifted:
        dd.observe(s)                             # window fully replaced
    drifted, metric = dd.check()
    assert drifted and metric > 0.2
    ref = score_distribution(base)
    assert ref["mean_score"] == pytest.approx(0.7)
    assert ref["bands"]["0.7"] == 1.0


def test_fleet_fault_env_composer_round_trips():
    env = fleet_fault_env(partition_mine=["m1", "m2"],
                          dup_manifest="m0", kill_train=(1, 0.5))
    assert env[ffleet.ENV_PARTITION_MINE] == "m1,m2"
    assert env[fcap.ENV_DUP_MANIFEST] == "m0"
    assert env[ffleet.ENV_KILL_TRAIN] == "1:0.5"
    fw = FleetFlywheel("/nonexistent", env=env)
    assert fw._partitioned == {"m1", "m2"}
    assert (fw._kill_round, fw._kill_after_s) == (1, 0.5)
    assert FleetFlywheel("/nonexistent", env={})._partitioned == set()


# -- the promotion gate, in-process ----------------------------------------


def _capture_engine_traffic(tmp_path, n=8):
    """Serve n requests through a REAL engine with capture on; returns
    (capture_dir, eval_shard_path) built from the mined hold-outs."""
    scfg = serve_cfg()
    d = str(tmp_path / "cap")
    pred = FakeServePredictor(scfg, {"scale": np.float32(1.0)})
    engine = ServeEngine(pred, scfg, ServeOptions(
        batch_size=2, max_delay_ms=1.0, max_queue=32))
    engine.capture = RequestCapture(CaptureOptions(
        capture_dir=d, shard_records=4, member="m0"))
    engine.start()
    try:
        futs = [engine.submit(raw_image(60 + i, 100 + i, 30 + 5 * i))
                for i in range(n)]
        for f in futs:
            assert f.result(timeout=30.0)
    finally:
        engine.stop()
    doc = next(iter(merge_manifests(d)["members"].values()))
    r = mine_member(d, doc, top_k=n, min_label_score=0.1)
    path, kept, _ = build_eval_shard(d, r["entries"][:4],
                                     str(tmp_path / "ev"))
    assert kept >= 1
    return d, path


def test_promotion_gate_accepts_beats_rejects_regression(tmp_path):
    """The PR-8 canary extended to a measured quality delta: a candidate
    matching the incumbent on the held-out shard promotes; a collapsed
    candidate is rolled back with the generation UNTOUCHED, and the
    engine keeps serving the incumbent's outputs."""
    telemetry.configure(str(tmp_path / "tel"), run_meta={"driver": "t"})
    _, eval_shard = _capture_engine_traffic(tmp_path)
    scfg = serve_cfg()
    pred = FakeServePredictor(scfg, {"scale": np.float32(1.0)})
    engine = ServeEngine(pred, scfg, ServeOptions(
        batch_size=2, max_delay_ms=1.0, max_queue=32)).start()
    try:
        good = str(tmp_path / "good.json")
        with open(good, "w") as fh:
            json.dump({"scale": 1.3}, fh)
        ok, info = rp.reload_engine_params(
            engine, pred, scfg,
            {"prefix": good, "kind": "file", "epoch": 1, "consumed": 0,
             "eval_shard": eval_shard, "quality_slack": 0.1},
            load_params_fn=load_params)
        assert ok, info
        assert info["quality_candidate"] >= info["quality_incumbent"] - 0.1
        assert info["quality_incumbent"] > 0.5    # incumbent agrees with
        gen = engine.generation                   # its own pseudo-labels
        assert gen >= 1
        before = engine.submit(raw_image(60, 100, 40)).result(timeout=30.0)
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as fh:
            json.dump({"scale": 0.004}, fh)       # quality-regressed save
        ok, info = rp.reload_engine_params(
            engine, pred, scfg,
            {"prefix": bad, "kind": "file", "epoch": 2, "consumed": 0,
             "eval_shard": eval_shard, "quality_slack": 0.0},
            load_params_fn=load_params)
        assert not ok and info["rolled_back"]
        assert info["quality_candidate"] < info["quality_incumbent"]
        assert engine.generation == gen           # never advanced
        after = engine.submit(raw_image(60, 100, 40)).result(timeout=30.0)
        assert after and after[0]["score"] == pytest.approx(
            before[0]["score"], abs=1e-5)         # incumbent still serving
        # fail CLOSED: an unreadable eval shard blocks the swap entirely
        ok, info = rp.reload_engine_params(
            engine, pred, scfg,
            {"prefix": good, "kind": "file", "epoch": 3, "consumed": 0,
             "eval_shard": str(tmp_path / "missing.json")},
            load_params_fn=load_params)
        assert not ok and "eval shard unreadable" in info["error"]
        assert not info["rolled_back"] and engine.generation == gen
    finally:
        engine.stop()
    telemetry.shutdown()
    flight = os.path.join(str(tmp_path / "tel"), "flight_0.jsonl")
    assert os.path.exists(flight)
    blob = open(flight).read()
    assert "promotion_rejected" in blob


def test_eval_shard_quality_scores_live_engine(tmp_path):
    _, eval_shard = _capture_engine_traffic(tmp_path)
    shard = load_eval_shard(eval_shard)
    scfg = serve_cfg()
    pred = FakeServePredictor(scfg, {"scale": np.float32(1.0)})
    engine = ServeEngine(pred, scfg, ServeOptions(
        batch_size=2, max_delay_ms=1.0, max_queue=32)).start()
    try:
        q = eval_shard_quality(engine, shard)
        assert q > 0.5                            # reproduces own labels
        pred.update_params({"scale": np.float32(0.004)})
        assert eval_shard_quality(engine, shard) < q
    finally:
        engine.stop()


# -- report plumbing ---------------------------------------------------------


def test_flywheel_counters_table_has_fleet_rows():
    from mx_rcnn_tpu.telemetry.report import FLYWHEEL_COUNTERS
    for key in ("flywheel/manifest_dup_dropped", "flywheel/promoted",
                "flywheel/rejected", "flywheel/drift_detected",
                "flywheel/promotion_gate_pass",
                "flywheel/promotion_gate_reject"):
        assert key in FLYWHEEL_COUNTERS


# -- chaos e2e: the acceptance pin -----------------------------------------


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _member_proc(port, index, member, capture_dir, env=None):
    argv = [sys.executable, WORKER, "--port", str(port),
            "--replica-index", str(index),
            "--capture-dir", capture_dir, "--capture-member", member,
            "--capture-shard-records", "4"]
    return subprocess.Popen(
        argv, env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


def _e2e_opts(**kw):
    base = dict(probe_interval_s=0.2, probe_timeout_s=2.0,
                evict_probes=2, start_timeout_s=120.0,
                backoff_base_s=0.2, backoff_max_s=1.0, stable_s=5.0,
                drain_timeout_s=15.0, reload_timeout_s=120.0)
    base.update(kw)
    return fb.FabricOptions(**base)


def _wait(cond, timeout=90.0, what=""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


def _cleanup(pool, procs):
    pool.stop()
    for p in procs:
        p.kill()
        p.wait(timeout=30)


TRAINER_SRC = """\
import argparse, json, os, time
ap = argparse.ArgumentParser()
ap.add_argument("--params-file", required=True)
ap.add_argument("--sleep", type=float, default=1.0)
ap.add_argument("--replay-manifest", required=True)
a = ap.parse_args()
assert os.path.exists(a.replay_manifest)
time.sleep(a.sleep)
tmp = a.params_file + ".tmp"
with open(tmp, "w") as fh:
    json.dump({"scale": 2.0}, fh)
os.replace(tmp, a.params_file)
"""


def test_fleet_chaos_e2e_converges_and_rejects_regression(tmp_path):
    """THE acceptance pin: 2 real TCP members share a capture dir under
    router traffic; the fleet loop runs with a partition mid-mine (m1),
    the round-0 trainer SIGKILLed mid-epoch, m0's first capture shard
    corrupted, and m0's manifest duplicate-delivered — and still
    converges to a promoted generation served by ALL members.  Then a
    quality-regressed candidate is rejected by the member-side gate and
    every member stays on the incumbent."""
    from mx_rcnn_tpu.serve import encode_image_payload

    capdir = str(tmp_path / "cap")
    os.makedirs(capdir)
    pfile = str(tmp_path / "params.json")
    trainer = str(tmp_path / "trainer.py")
    with open(trainer, "w") as fh:
        fh.write(TRAINER_SRC)
    ports = [_free_port(), _free_port()]
    procs = [
        _member_proc(ports[0], 0, "m0", capdir,
                     env={**flywheel_fault_env(corrupt_shard=0),
                          **fleet_fault_env(dup_manifest="m0")}),
        _member_proc(ports[1], 1, "m1", capdir),
    ]
    pool = fb.ReplicaPool(_e2e_opts())
    for port in ports:
        pool.register(f"127.0.0.1:{port}")
    pool.start()
    try:
        _wait(lambda: pool.ready_count() == 2, what="both members ready")
        router = fb.FabricRouter(pool, timeout_s=30.0)
        body = json.dumps(encode_image_payload(
            np.full((60, 100, 3), 50, np.uint8))).encode()

        def both_members_spilled():
            status, _, _ = router.route_predict(body)
            assert status in (200, 503)
            docs = merge_manifests(capdir)["members"].values()
            per = {d["member"]: len(d["shards"]) for d in docs}
            return per.get("m0", 0) >= 2 and per.get("m1", 0) >= 2

        _wait(both_members_spilled, timeout=90.0,
              what="both members to spill 2+ capture shards")

        fleet = FleetFlywheel(
            capdir, top_k=12, min_label_score=0.1,
            train_cmd=[sys.executable, trainer, "--params-file", pfile,
                       "--sleep", "1.0"],
            candidate_fn=None, rollout_fn=pool.reload_to,
            eval_every=3, quality_slack=0.3,
            env=fleet_fault_env(partition_mine="m1",
                                kill_train=(0, 0.25)))
        epoch = {"n": 0}

        def candidate_fn():
            if not os.path.exists(pfile):
                return None
            epoch["n"] += 1
            return {"prefix": pfile, "kind": "file",
                    "epoch": epoch["n"], "consumed": 0}

        fleet.candidate_fn = candidate_fn
        results = fleet.run(max_rounds=3)
        # round 0: trainer SIGKILLed mid-epoch → negative rc, no promote
        assert results[0]["train_rc"] not in (None, 0)
        assert not results[0]["promoted"]
        # the partitioned member cost its ranking, never the round
        assert results[0]["mine_failed"] == ["m1"]
        assert results[0]["members"] == ["m0"]
        # duplicate delivery folded, not double-counted
        assert results[0]["duplicates_dropped"] >= 1
        # CONVERGENCE: a later round promotes fleet-wide anyway
        assert fleet.promoted_rounds == 1
        final = results[-1]
        assert final["promoted"] and final["train_rc"] == 0
        assert pool.generation >= 1
        gens = pool.member_generations()
        assert len(gens) == 2
        assert all(g == pool.generation for g in gens.values()), gens
        promoted_gen = pool.generation

        # REJECTION: a quality-regressed generation must never be
        # served by any member.  Gate on a hold-out shard built from
        # the mined entries (corrupt-shard records skipped).
        with open(final["manifest"]) as fh:
            entries = json.load(fh)["entries"]
        ev_path, kept, _ = build_eval_shard(capdir, entries,
                                            str(tmp_path / "reject-ev"))
        assert ev_path and kept >= 1
        badfile = str(tmp_path / "bad.json")
        with open(badfile, "w") as fh:
            json.dump({"scale": 0.004}, fh)
        ok = pool.reload_to({"prefix": badfile, "kind": "file",
                             "epoch": 99, "consumed": 0,
                             "eval_shard": ev_path,
                             "quality_slack": 0.0})
        assert not ok
        assert pool.counters["quality_rejected"] >= 1
        assert pool.generation == promoted_gen
        assert all(g == promoted_gen
                   for g in pool.member_generations().values())
        # every member still answers with the incumbent weights
        status, _, _ = router.route_predict(body)
        assert status == 200
    finally:
        _cleanup(pool, procs)
