"""Cascade serving tier-1 tests (CPU) — the ISSUE-19 contracts.

The :class:`~mx_rcnn_tpu.serve.pool.CascadeRouter` pins from seven
angles: (1) the shared hardness definition — the jitted device gate
agrees with the miner's host scoring on identical detections, and the
miner imports the SAME function object (no drift possible); (2) the
threshold sweep — ``thresh=0`` escalates everything (and the escalated
answers equal direct big-model submits), ``thresh=1`` escalates
nothing, counts are monotone in between; (3) cascade-off byte parity —
a server without a router returns exactly the pre-cascade response
shape; (4) zero steady-state recompiles — post-warmup traffic with
escalations in the mix compiles nothing new on either engine or
registry; (5) escalated frames land in the capture ring tagged
``cascade_escalated`` with the big model's records; (6) a tenant with
``fidelity="full"`` pins to the big model (and a non-cascade sibling
bypasses untouched); (7) the whole thing end-to-end over a unix socket,
the responses' provenance and the router's counters agreeing.

The real-model fixture is module-scoped: two synthetic-weight e2e
engines (distinct config digests — the realistic small/big deployment
shape on one chip) built once and shared by every gate-path test.
"""

import dataclasses
import json
import threading

import numpy as np
import pytest

from mx_rcnn_tpu.flywheel.capture import (CaptureOptions, RequestCapture,
                                          list_shards, score_stats)
from mx_rcnn_tpu.flywheel.hardness import (HARDNESS_MAX,
                                           build_device_hardness, hardness,
                                           hardness_from_records)
from mx_rcnn_tpu.serve import (CascadeRouter, ModelPool, ServeEngine,
                               ServeOptions, encode_image_payload,
                               make_server, unix_http_request, warmup)
from tests.test_multimodel import add_fake_model
from tests.test_serve import make_engine, tiny_cfg

def set_thresh(router, t):
    """Retune a live router (what a config push would do); rebuilding a
    router would re-register the gate program, so tests retune."""
    router.thresh = float(t)
    router._thresh_raw = float(t) * HARDNESS_MAX


# -- (1) shared hardness: device gate == host miner ------------------------


def test_device_hardness_matches_host_reference():
    cases = [
        [],                                  # failed/empty frame
        [0.9],                               # one confident detection
        [0.5, 0.5, 0.5, 0.5],                # uniform mass: entropy = 1
        [0.95, 0.6, 0.35, 0.12, 0.05],       # mixed bands
        [0.31, 0.69, 0.71, 0.29, 0.5, 0.5],  # scores straddling bands
    ]
    cap = 8
    dets = np.zeros((len(cases), cap, 6), np.float32)
    valid = np.zeros((len(cases), cap), bool)
    for b, scores in enumerate(cases):
        for j, s in enumerate(scores):
            dets[b, j, 4] = s
            valid[b, j] = True
    dev = np.asarray(build_device_hardness()(dets, valid))
    assert dev.shape == (len(cases),)
    for b, scores in enumerate(cases):
        records = [{"cls": 1, "score": s, "bbox": [0.0, 0.0, 4.0, 4.0]}
                   for s in scores]
        host = hardness_from_records(records)
        # float32 device vs float64 host
        assert abs(float(dev[b]) - host) < 5e-5, (b, float(dev[b]), host)
        assert 0.0 <= float(dev[b]) < HARDNESS_MAX


def test_miner_and_gate_share_one_hardness():
    from mx_rcnn_tpu.flywheel import miner

    # the miner scores with the SAME function object the shared module
    # exports — a fork would break this identity, not just a tolerance
    assert miner.hardness is hardness
    records = [{"cls": 2, "score": s, "bbox": [0, 0, 1, 1]}
               for s in (0.8, 0.45, 0.2)]
    score, parts = hardness(score_stats(records))
    assert score == pytest.approx(hardness_from_records(records))
    assert set(parts) == {"entropy", "disagreement", "low_max"}


# -- the real-model cascade pair (module-scoped, built once) ---------------


@pytest.fixture(scope="module")
def cascade_pool():
    import jax

    from mx_rcnn_tpu.compile import config_digest
    from mx_rcnn_tpu.eval import Predictor
    from mx_rcnn_tpu.models import build_model, init_params
    from mx_rcnn_tpu.train.checkpoint import denormalize_for_save

    cfg_small = tiny_cfg()
    # the big model is a different deployment of the same network
    # (distinct digest, same SCALES so bucket geometry agrees — the
    # router's escalation precondition)
    cfg_big = tiny_cfg().replace(
        TEST=dataclasses.replace(tiny_cfg().TEST, NMS=0.31))
    assert config_digest(cfg_small) != config_digest(cfg_big)

    pool = ModelPool().start()
    for i, (mid, cfg) in enumerate((("small", cfg_small), ("big", cfg_big))):
        model = build_model(cfg)
        params = denormalize_for_save(
            init_params(model, cfg, jax.random.PRNGKey(i), 2, (96, 128)),
            cfg)
        pred = Predictor(model, params, cfg)
        engine = ServeEngine(pred, cfg, ServeOptions(
            batch_size=2, max_delay_ms=5.0, max_queue=32, serve_e2e=True))
        engine.start(external=True)
        pool.add_model(mid, cfg, pred, engine)
        assert warmup(engine) == 2  # one fused program per orientation
    router = CascadeRouter(pool, "small", "big", thresh=0.5)
    assert router.warmup() == 1     # the gate program, compiled pre-traffic
    pool.cascade = router
    yield pool, router
    pool.stop()


def _mixed_images(rng, n=4):
    shapes = ((60, 100), (100, 60), (48, 90), (90, 48))
    return [rng.randint(0, 255, shapes[i % 4] + (3,), dtype=np.uint8)
            for i in range(n)]


# -- (2) threshold sweep ---------------------------------------------------


def test_threshold_sweep_monotonic(cascade_pool):
    pool, router = cascade_pool
    rng = np.random.RandomState(3)
    imgs = _mixed_images(rng, 4)
    counts, records = {}, {}
    try:
        for t in (0.0, 0.5, 1.0):
            set_thresh(router, t)
            base = dict(router.counters)
            futs = [router.submit(img) for img in imgs]
            records[t] = [f.result(timeout=300) for f in futs]
            esc = router.counters["escalated"] - base["escalated"]
            small = (router.counters["answered_small"]
                     - base["answered_small"])
            assert esc + small == len(imgs)
            counts[t] = esc
            for f in futs:
                prov = f.provenance()
                assert prov["thresh"] == t
                assert prov["escalated"] == (prov["model"] == "big")
                assert 0.0 <= prov["hardness"] < HARDNESS_MAX
    finally:
        set_thresh(router, 0.5)

    # thresh 0 escalates everything, 1 nothing, monotone in between
    assert counts[0.0] == len(imgs)
    assert counts[1.0] == 0
    assert counts[0.0] >= counts[0.5] >= counts[1.0]

    # thresh=0 answers ARE the big model's: identical to direct submits
    # of the same raw images (escalation reuses the staged pixels)
    big = pool.engine_for("big")
    for img, got in zip(imgs, records[0.0]):
        ref = big.submit(img).result(timeout=300)
        assert len(got) == len(ref)
        for d, e in zip(got, ref):
            assert d["cls"] == e["cls"]
            assert abs(d["score"] - e["score"]) < 1e-3
            assert np.allclose(d["bbox"], e["bbox"], atol=0.1)


# -- (3) cascade-off byte parity -------------------------------------------


def test_cascade_off_response_byte_parity(tmp_path):
    eng = make_engine(tiny_cfg()).start()
    sock = str(tmp_path / "plain.sock")
    server = make_server(eng, unix_socket=sock)
    th = threading.Thread(target=server.serve_forever, daemon=True)
    th.start()
    try:
        img = np.full((60, 100, 3), 7, np.uint8)
        status, resp = unix_http_request(
            sock, "POST", "/predict", encode_image_payload(img), timeout=60)
        assert status == 200
        # EXACTLY the pre-cascade shape: no "cascade" provenance field
        assert set(resp) == {"detections", "queue_wait_ms"}
        status, m = unix_http_request(sock, "GET", "/metrics")
        assert status == 200 and "cascade" not in m
    finally:
        server.shutdown()
        server.server_close()
        eng.stop()


# -- (4) zero steady-state recompiles --------------------------------------


def test_zero_recompiles_with_escalations(cascade_pool):
    pool, router = cascade_pool
    regs = {mid: pool.engine_for(mid).registry for mid in ("small", "big")}
    programs = {mid: regs[mid].counters["programs"] for mid in regs}
    engines = {mid: dict(pool.engine_for(mid).counters)
               for mid in ("small", "big")}
    gate_batches = router.counters["gate_batches"]

    rng = np.random.RandomState(7)
    set_thresh(router, 0.0)  # force escalations into the steady state
    try:
        for _ in range(2):
            futs = [router.submit(img) for img in _mixed_images(rng, 4)]
            for f in futs:
                assert f.result(timeout=300) is not None
    finally:
        set_thresh(router, 0.5)

    assert router.counters["gate_batches"] > gate_batches
    for mid in ("small", "big"):
        assert regs[mid].counters["programs"] == programs[mid], mid
        c = pool.engine_for(mid).counters
        assert c["recompiles"] == engines[mid]["recompiles"], mid
        assert c["recompiles"] == c["warmup_programs"], mid
    # the gate is a registry citizen: kind-labeled beside the fused
    # serving programs in the small model's compile snapshot
    rows = pool.engine_for("small").metrics()["compile"]["programs"]
    assert sum(p["kind"] == CascadeRouter.KIND for p in rows) == 1


# -- (5) capture-ring tagging ----------------------------------------------


def test_escalated_frames_feed_capture_tagged(cascade_pool, tmp_path):
    pool, router = cascade_pool
    cap_dir = str(tmp_path / "cap")
    cap = RequestCapture(CaptureOptions(
        capture_dir=cap_dir, sample_every=1, shard_records=4,
        member="cascade_test"))
    old_cap = router.capture
    rng = np.random.RandomState(5)
    set_thresh(router, 0.0)  # every frame escalates
    try:
        router.capture = cap
        futs = [router.submit(img) for img in _mixed_images(rng, 4)]
        for f in futs:
            f.result(timeout=300)
        cap.flush()
    finally:
        router.capture = old_cap
        set_thresh(router, 0.5)

    shards = list_shards(cap_dir)
    assert shards, "escalated frames must spill capture shards"
    rows = [json.loads(line)
            for s in shards for line in open(s["jsonl"]) if line.strip()]
    assert len(rows) == 4
    big_gen = pool.engine_for("big").generation
    for r in rows:
        # additively tagged: the legacy meta fields all still present
        assert r["tags"] == ["cascade_escalated"]
        assert r["generation"] == big_gen  # big model's pseudo-labels
        assert "stats" in r and "detections" in r and "bucket" in r


# -- (6) per-tenant fidelity pin -------------------------------------------


def test_fidelity_full_pins_tenant_to_big(cascade_pool):
    pool, router = cascade_pool
    cfg = tiny_cfg()
    add_fake_model(pool, cfg, "vip", fidelity="full")
    add_fake_model(pool, cfg, "bystander")  # default fidelity="cascade"

    img = np.full((60, 100, 3), 9, np.uint8)
    big = pool.engine_for("big")
    base_forced = router.counters["forced_big"]
    base_big_requests = big.counters["requests"]

    fut = router.submit(img, model_id="vip")
    assert fut.result(timeout=300) is not None
    assert fut.provenance() == {"model": "big", "escalated": False,
                                "reason": "fidelity"}
    assert router.counters["forced_big"] == base_forced + 1
    assert big.counters["requests"] == base_big_requests + 1

    # a pool sibling outside the pair bypasses the cascade untouched
    bys = pool.engine_for("bystander")
    base_bys = bys.counters["requests"]
    fut = router.submit(img, model_id="bystander")
    assert fut.result(timeout=60) is not None
    assert fut.provenance() == {"model": "bystander", "escalated": False,
                                "reason": "bypass"}
    assert bys.counters["requests"] == base_bys + 1
    assert big.counters["requests"] == base_big_requests + 1

    # addressing the big model directly is served, not re-gated
    fut = router.submit(img, model_id="big")
    assert fut.result(timeout=300) is not None
    assert fut.provenance() == {"model": "big", "escalated": False,
                                "reason": "addressed"}
    assert router.counters["forced_big"] == base_forced + 1


# -- (7) two real models e2e over a socket ---------------------------------


def test_cascade_e2e_over_socket(cascade_pool, tmp_path):
    """The only test that drives two real models through the cascade over
    a socket: every request answered, every answer says which model gave
    it and why, and the router's own counters add up to the traffic."""
    from benchmark.loadgen import http_unix

    pool, router = cascade_pool
    sock = str(tmp_path / "cascade.sock")
    server = make_server(pool.engine_for(), unix_socket=sock, pool=pool,
                         cascade=router)
    th = threading.Thread(target=server.serve_forever, daemon=True)
    th.start()
    rng = np.random.RandomState(11)
    gated = [json.dumps(encode_image_payload(img)).encode()
             for img in _mixed_images(rng, 6)]
    direct = [json.dumps({**encode_image_payload(img), "model": "big"}
                         ).encode() for img in _mixed_images(rng, 2)]
    base = dict(router.counters)
    big_requests = pool.engine_for("big").counters["requests"]
    try:
        replies = [http_unix(sock, "POST", "/predict", body, timeout=300)
                   for body in gated + direct]
        status, metrics = http_unix(sock, "GET", "/metrics")
    finally:
        server.shutdown()
        server.server_close()

    assert [st for st, _ in replies] == [200] * 8
    provs = [doc["cascade"] for _, doc in replies]
    for prov in provs[:6]:      # gated: the hardness decided
        assert prov["model"] in ("small", "big")
        assert prov["escalated"] == (prov["model"] == "big")
        assert prov["thresh"] == router.thresh
        assert 0.0 <= prov["hardness"] < HARDNESS_MAX
    for prov in provs[6:]:      # addressed to the big model: never gated
        assert prov == {"model": "big", "escalated": False,
                        "reason": "addressed"}
    escalated = sum(p["escalated"] for p in provs)
    delta = {k: router.counters[k] - base[k] for k in base}
    assert delta["escalated"] == escalated
    assert delta["answered_small"] == 6 - escalated
    assert delta["escalation_rejected"] == 0
    assert delta["gate_batches"] >= 1
    # the big engine saw the escalations and the two addressed to it
    assert (pool.engine_for("big").counters["requests"] - big_requests
            == escalated + 2)
    # and /metrics carries the router's own account of the same traffic
    assert status == 200
    assert metrics["cascade"]["counters"] == dict(router.counters)
    assert metrics["cascade"]["small"] == "small"
    assert metrics["cascade"]["big"] == "big"
    assert 0.0 <= metrics["cascade"]["escalation_rate"] <= 1.0
