"""Distributed request tracing (ISSUE 16): cross-hop trace context,
batch-causality spans, tail-sampled forensics.

Four layers, mirroring the subsystem split:

* **Context** — the ``X-Mxr-Trace`` header grammar round trip
  (trace / trace-span / trace-span-flags, all-zero span = no parent,
  flags 00 = unsampled), child derivation, malformed → None.
* **Tracer** — span records in the telemetry JSONL schema (additive
  ``kind: "span"`` fields), the tail verdict (errored / non-200 /
  hedged-retried-shed always kept; slow kept against the windowed-p99
  of ROOT durations with the observe-after-verdict cold-start rule),
  atomic tail dumps, per-trace span budget, and the NULL-tracer
  zero-overhead pin (a tracing-off hot path that ever mints or records
  RAISES — the ``NULL_CAPTURE`` contract).
* **Hot-path inertness** — tracing off, a real engine round trip via
  ``handle_request_doc`` produces a response identical to the traced
  shape minus exactly the ``"trace"`` echo key, emits zero span events,
  and exposes no ``trace`` metrics section.
* **End to end** — one client-minted trace id through a REAL two-member
  TCP fabric (``tests/fabric_worker.py`` subprocesses with
  ``MXR_TRACE_DIR`` opt-in + an in-process router tracer): the id is
  queryable across ≥3 hop types and ≥2 members by merging the
  per-member span files, exactly as ``scripts/trace_query.py`` does.
"""

import importlib.util
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.serve import encode_image_payload
from mx_rcnn_tpu.serve import fabric as fb
from mx_rcnn_tpu.serve.frontend import handle_request_doc
from mx_rcnn_tpu.telemetry import tracectx
from mx_rcnn_tpu.telemetry.tracectx import (NULL_SPAN, NULL_TRACER,
                                            SPANS_PREFIX, TAIL_PREFIX,
                                            TraceContext, Tracer)
from tests.test_serve import make_engine, raw_image, tiny_cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "fabric_worker.py")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _restore_tracer():
    yield
    tracectx.shutdown()
    telemetry.shutdown()


# -- context grammar --------------------------------------------------------


def test_context_parse_grammar_and_header_round_trip():
    t = "ab" * 16
    s = "cd" * 8
    full = TraceContext.parse(f"{t}-{s}-01")
    assert (full.trace_id, full.span_id, full.sampled) == (t, s, True)
    assert TraceContext.parse(full.to_header()).span_id == s
    # bare id and all-zero span id both mean "no parent yet": the first
    # span recorded under them is the trace's ROOT
    assert TraceContext.parse(t).span_id is None
    assert TraceContext.parse(f"{t}-{'0' * 16}-01").span_id is None
    # flags 00 = unsampled propagation
    assert TraceContext.parse(f"{t}-{s}-00").sampled is False
    # malformed → None (a frontend mints fresh, never serves garbage)
    for bad in ("", "xyz", "12", f"{t}-GG", f"{t}-{s}-01-extra", 7, None):
        assert TraceContext.parse(bad) is None
    child = full.child()
    assert child.trace_id == t and child.span_id != s
    assert len(child.span_id) == 16


def test_null_tracer_raises_and_null_span_is_inert():
    """The zero-overhead pin: the disabled tracer's recording methods
    RAISE, so surviving a tracing-off round trip proves the hot path
    paid only the ``enabled`` check."""
    assert tracectx.get() is NULL_TRACER
    assert not NULL_TRACER.enabled
    with pytest.raises(RuntimeError, match="disabled"):
        NULL_TRACER.mint()
    with pytest.raises(RuntimeError, match="disabled"):
        NULL_TRACER.span(None, "x")
    with pytest.raises(RuntimeError, match="disabled"):
        NULL_TRACER.record(None, "x", 0.0)
    with NULL_SPAN as sp:
        sp.set(anything="goes")
    assert NULL_SPAN.ctx is None


# -- tracer sink ------------------------------------------------------------


def test_spans_stream_in_telemetry_schema_with_parentage(tmp_path):
    tr = tracectx.configure(str(tmp_path), member="m0", sample=1.0)
    ctx = tr.mint()
    with tr.span(ctx, "fabric/route") as sp:
        child_ctx = sp.ctx
        with tr.span(child_ctx, "frontend/predict") as sp2:
            sp2.set(status=200)
        sp.set(member="m1", status=200)
    path = os.path.join(str(tmp_path), f"{SPANS_PREFIX}m0.jsonl")
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert [r["name"] for r in recs] == ["frontend/predict", "fabric/route"]
    inner, root = recs
    for r in recs:
        # additive fields on the v1 schema: old readers key on "kind"
        assert r["kind"] == "span" and r["v"] == 1
        assert r["trace"] == ctx.trace_id and r["member"] == "m0"
        assert r["dur_s"] >= 0.0 and "ts" in r
    assert "psid" not in root                  # minted ctx → true root
    assert inner["psid"] == root["sid"] == child_ctx.span_id
    assert inner["attrs"]["status"] == 200
    assert root["attrs"]["member"] == "m1"
    m = tr.metrics()
    assert m["spans_emitted"] == 2 and m["live_traces"] == 0


def test_unsampled_context_records_nothing(tmp_path):
    tr = tracectx.configure(str(tmp_path), member="m0", sample=0.0)
    ctx = tr.mint()                            # sample=0 → unsampled mint
    assert not ctx.sampled
    assert tr.span(ctx, "fabric/route") is NULL_SPAN
    assert tr.record(ctx, "x", 0.1) is None
    assert tr.record(None, "x", 0.1) is None
    assert tr.metrics()["spans_emitted"] == 0


def test_span_exception_lands_as_error_attr_and_is_tail_kept(tmp_path):
    tr = tracectx.configure(str(tmp_path), member="m0")
    with pytest.raises(ValueError):
        with tr.span(tr.mint(), "frontend/predict"):
            raise ValueError("boom")
    tail = os.path.join(str(tmp_path), f"{TAIL_PREFIX}m0.jsonl")
    with open(tail) as f:
        rec = json.loads(f.readline())
    assert rec["attrs"]["error"].startswith("ValueError: boom")
    assert tr.metrics()["tail_kept"] == 1


def test_tail_verdict_slow_errored_and_flagged_roots(tmp_path):
    """Cold-start observe-after-verdict: the FIRST clean root has no
    window yet and is dropped; after a fast population, a slow root (≥
    the windowed p99) is kept, as are non-200 and hedged roots at any
    speed."""
    tr = tracectx.configure(str(tmp_path), member="m0")
    tr.record(tr.mint(), "root", 0.001, attrs={"status": 200})
    assert tr.metrics()["tail_kept"] == 0      # no window on request #1
    for _ in range(8):
        tr.record(tr.mint(), "root", 0.001, attrs={"status": 200})
    kept_before = tr.metrics()["tail_kept"]
    tr.record(tr.mint(), "root", 2.0, attrs={"status": 200})   # slow
    assert tr.metrics()["tail_kept"] == kept_before + 1
    tr.record(tr.mint(), "root", 0.0001, attrs={"status": 503})
    tr.record(tr.mint(), "root", 0.0001, attrs={"hedged": True})
    assert tr.metrics()["tail_kept"] == kept_before + 3
    # the dump is a complete, parseable snapshot of the kept ring
    tail = os.path.join(str(tmp_path), f"{TAIL_PREFIX}m0.jsonl")
    with open(tail) as f:
        rows = [json.loads(line) for line in f]
    assert len(rows) == kept_before + 3
    assert not [n for n in os.listdir(str(tmp_path)) if ".tmp." in n]


def test_per_trace_span_budget_drops_not_grows(tmp_path):
    tr = tracectx.configure(str(tmp_path), member="m0")
    ctx = tr.mint().child()                    # non-root: never finalizes
    for _ in range(tracectx.MAX_SPANS_PER_TRACE + 5):
        tr.record(ctx, "loop", 0.001)
    m = tr.metrics()
    assert m["spans_emitted"] == tracectx.MAX_SPANS_PER_TRACE
    assert m["spans_dropped"] == 5 and m["live_traces"] == 1


def test_configure_from_env_opt_in_and_no_op(tmp_path, monkeypatch):
    monkeypatch.delenv(tracectx.ENV_TRACE_DIR, raising=False)
    assert tracectx.configure_from_env(member="m9") is None
    assert tracectx.get() is NULL_TRACER
    monkeypatch.setenv(tracectx.ENV_TRACE_DIR, str(tmp_path))
    monkeypatch.setenv(tracectx.ENV_TRACE_SAMPLE, "0.25")
    tr = tracectx.configure_from_env(member="m9", rank=3)
    assert tr is tracectx.get() and tr.enabled
    assert tr.member == "m9" and tr.rank == 3
    assert tr.sample == pytest.approx(0.25)
    # second call is a no-op while a tracer is live (serve.py configures
    # first; serve_replica's env hook must not clobber it)
    assert tracectx.configure_from_env(member="other") is None
    assert tracectx.get() is tr


# -- hot-path inertness (tracing off) ---------------------------------------


def test_tracing_off_predict_is_byte_identical_minus_echo(tmp_path):
    """The acceptance pin: with tracing off, a /predict response with a
    client-minted id differs from the untraced response by EXACTLY the
    ``"trace"`` echo key; no span file is written, no trace metrics
    section appears, and the engine's hot path never reached the (raising)
    NULL tracer."""
    assert tracectx.get() is NULL_TRACER
    engine = make_engine(tiny_cfg()).start()
    try:
        doc = encode_image_payload(raw_image(60, 100, 40))
        status_a, resp_a = handle_request_doc(engine, dict(doc))
        tid = "ab" * 16
        status_b, resp_b = handle_request_doc(engine, dict(doc, trace=tid))
        assert status_a == status_b == 200
        assert "trace" not in resp_a
        assert resp_b.pop("trace") == tid
        assert resp_a["detections"] == resp_b["detections"]
        assert set(resp_a) == set(resp_b)
        # header form echoes just the trace id, not the span suffix
        _, resp_c = handle_request_doc(
            engine, dict(doc), trace_header=f"{tid}-{'cd' * 8}-01")
        assert resp_c["trace"] == tid
        assert "trace" not in engine.metrics()
    finally:
        engine.stop()
    assert not [n for n in os.listdir(str(tmp_path))
                if n.startswith((SPANS_PREFIX, TAIL_PREFIX))]


# -- engine batch-causality -------------------------------------------------


def test_engine_batch_causality_spans(tmp_path):
    """Three same-bucket requests coalesced into one batch: each traced
    request's ``engine/request`` span names its batch peers, queue
    position, and pad fraction; the ``engine/dispatch`` child names every
    rid that shared the program run; phase children hang below it."""
    tr = tracectx.configure(str(tmp_path), member="m0")
    engine = make_engine(tiny_cfg(), batch_size=4, max_delay_ms=200,
                         max_queue=16).start()
    try:
        ctxs = [tr.mint() for _ in range(3)]
        futs = [engine.submit(raw_image(60, 100, 30 + 5 * i), trace=c)
                for i, c in enumerate(ctxs)]
        for f in futs:
            assert f.result(timeout=30.0)
        # spans land on the flush tail AFTER the futures resolve: wait
        # for every request's engine/request + engine/dispatch pair
        _wait(lambda: tr.metrics()["spans_emitted"] >= 6,
              timeout=30.0, what="batch-causality spans")
        assert engine.metrics()["trace"]["spans_emitted"] >= 6
    finally:
        engine.stop()
    with open(os.path.join(str(tmp_path), f"{SPANS_PREFIX}m0.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    by_trace = {}
    for r in recs:
        by_trace.setdefault(r["trace"], {})[r["name"]] = r
    assert set(by_trace) == {c.trace_id for c in ctxs}
    all_rids = set()
    for ctx in ctxs:
        tree = by_trace[ctx.trace_id]
        req = tree["engine/request"]
        disp = tree["engine/dispatch"]
        a = req["attrs"]
        all_rids.add(a["rid"])
        assert set(a["peers"]) == {r2["attrs"]["rid"]
                                   for t2, r2 in (
                                       (t, by_trace[t]["engine/request"])
                                       for t in by_trace)
                                   if t2 != ctx.trace_id}
        assert 0 <= a["queue_pos"] < 3 and a["queue_wait_ms"] >= 0.0
        assert a["pad_frac"] == pytest.approx(0.25)    # 3 of 4 rows live
        assert a["occupancy"] == "3/4" and a["bucket"]
        # dispatch is the request span's child and names the whole batch
        assert disp["psid"] == req["sid"]
        assert set(disp["attrs"]["batch_rids"]) >= {a["rid"], *a["peers"]}
        # at least one measured phase child hangs off the dispatch
        phases = [r for r in recs if r["trace"] == ctx.trace_id
                  and r.get("psid") == disp["sid"]]
        assert {p["name"] for p in phases} <= {
            "engine/h2d", "engine/forward", "engine/readback",
            "engine/postprocess"}
        assert phases
    assert len(all_rids) == 3


# -- query tool -------------------------------------------------------------


def test_trace_query_merges_dedupes_and_renders(tmp_path):
    tq = _load_script("trace_query")
    tr = tracectx.configure(str(tmp_path), member="m0")
    ctx = tr.mint()
    with tr.span(ctx, "fabric/route") as sp:
        with tr.span(sp.ctx, "frontend/predict") as sp2:
            sp2.set(status=503)                # non-200 root → tail kept
        sp.set(status=503)
    fast = tr.mint()
    tr.record(fast, "fabric/route", 0.0001, attrs={"status": 200})
    tracectx.shutdown()

    spans = tq.load_spans(str(tmp_path))
    traces = tq.group_traces(spans)
    # the kept trace appears in BOTH streams but dedupes to one tree
    assert len(traces[ctx.trace_id]) == 2
    lines = [tq.summary_line(ctx.trace_id, traces[ctx.trace_id])]
    tq.render_tree(traces[ctx.trace_id], lines)
    text = "\n".join(lines)
    assert "fabric/route" in text and "frontend/predict" in text
    assert "status=503" in text and "[m0]" in text
    # prefix resolution: unique prefix hits, ambiguous/missing raise
    assert tq.resolve_ids(traces, [ctx.trace_id[:10]]) == [ctx.trace_id]
    with pytest.raises(SystemExit, match="no trace"):
        tq.resolve_ids(traces, ["ffffffffff"])
    # an orphan (parent span never landed) surfaces as an extra root
    orphan = {"trace": ctx.trace_id, "sid": "aa" * 8, "psid": "bb" * 8,
              "name": "engine/request", "dur_s": 0.1, "member": "m1",
              "kind": "span"}
    roots = tq.roots_of(traces[ctx.trace_id] + [orphan])
    assert orphan in roots and len(roots) == 2


# -- end to end: one trace id across a real two-member fabric ---------------


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait(cond, timeout=90.0, what=""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


def test_e2e_one_trace_id_across_router_and_members(tmp_path):
    """The acceptance pin: a client-minted trace id sent through a REAL
    router + two REAL TCP member subprocesses (tracing opted in via
    ``MXR_TRACE_DIR``) is queryable end to end — ≥3 hop types across ≥2
    members under ONE id — by merging the per-member span files the way
    ``scripts/trace_query.py`` does."""
    trace_dir = str(tmp_path / "traces")
    ports = [_free_port(), _free_port()]
    procs = [subprocess.Popen(
        [sys.executable, WORKER, "--port", str(ports[i]),
         "--replica-index", str(i)],
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             tracectx.ENV_TRACE_DIR: trace_dir,
             tracectx.ENV_TRACE_MEMBER: f"member{i}"})
        for i in range(2)]
    tracectx.configure(trace_dir, member="router")
    pool = fb.ReplicaPool(fb.FabricOptions(
        probe_interval_s=0.2, probe_timeout_s=2.0, evict_probes=2,
        start_timeout_s=120.0, backoff_base_s=0.2, backoff_max_s=1.0,
        stable_s=5.0, drain_timeout_s=15.0, reload_timeout_s=60.0))
    for port in ports:
        pool.register(f"127.0.0.1:{port}")
    pool.start()
    tq = _load_script("trace_query")
    try:
        _wait(lambda: pool.ready_count() == 2, what="both members ready")
        router = fb.FabricRouter(pool, timeout_s=30.0)
        doc = encode_image_payload(raw_image(60, 100, 50))
        tids = []
        for i in range(4):
            tid = os.urandom(16).hex()
            body = json.dumps(dict(doc, trace=tid)).encode()
            status, raw, _ = router.route_predict(body)
            assert status == 200, raw
            # the member echoes the SAME id back through the router: the
            # cross-host correlation handle the client keys on
            assert json.loads(raw)["trace"] == tid
            tids.append(tid)

        def landed():
            traces = tq.group_traces(tq.load_spans(trace_dir))
            return all(
                t in traces
                and len({r["name"] for r in traces[t]}) >= 3
                and len({r["member"] for r in traces[t]}) >= 2
                for t in tids)

        # member span files flush per record but land asynchronously
        # with the response
        _wait(landed, timeout=30.0, what="spans from every hop on disk")
        traces = tq.group_traces(tq.load_spans(trace_dir))
        for tid in tids:
            recs = traces[tid]
            names = {r["name"] for r in recs}
            assert {"fabric/route", "frontend/predict",
                    "engine/request"} <= names
            members = {r["member"] for r in recs}
            assert "router" in members
            assert members & {"member0", "member1"}
            # parentage is a single connected tree: the router's route
            # span is the ONE true root
            roots = tq.roots_of(recs)
            assert [r["name"] for r in roots] == ["fabric/route"]
            # the member-side frontend span hangs off the router's span
            route = roots[0]
            fronts = [r for r in recs if r["name"] == "frontend/predict"]
            assert any(r.get("psid") == route["sid"] for r in fronts)
        # the tree renders as one indented multi-member hop tree
        lines = []
        tq.render_tree(traces[tids[0]], lines)
        text = "\n".join(lines)
        assert "fabric/route" in text and "engine/request" in text
    finally:
        pool.stop()
        for p in procs:
            p.kill()
            p.wait(timeout=30)
