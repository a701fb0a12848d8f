"""Dependency-free serving frontends over :class:`ServeEngine`.

Three transports, one JSON contract:

* TCP HTTP (``make_server(engine, port=...)``) — the production-shaped
  endpoint; fabric members are reached over it.
* Unix-socket HTTP (``make_server(engine, unix_socket=path)``) — same
  handler over ``AF_UNIX``; what the tier-1 tests round-trip (no port
  allocation races on shared CI hosts).  ``unix_http_request`` is the
  matching client.
* stdio (``run_stdio``) — newline-delimited JSON over stdin/stdout for
  debugging and pipe-based harnesses.

Endpoints:

* ``POST /predict`` — body ``{"shape": [h, w, 3], "data": <base64 raw
  uint8 RGB bytes>}`` (or ``"pixels"``: nested lists), optional
  ``"deadline_ms"``.  200 → ``{"detections": [{"cls", "score", "bbox"}...],
  "queue_wait_ms"}``; 503 queue full (backpressure — retry with backoff);
  504 deadline exceeded; 400 malformed.
* ``POST /stream`` — sequenced-frame streaming (only when the server was
  built with a ``stream`` manager; 404 otherwise).  Body is NDJSON: one
  frame per line, each a predict payload plus ``"stream_id"`` (str) and
  ``"seq"`` (strictly increasing int per stream).  The connection is
  persistent (HTTP/1.1 keep-alive) and a body may carry many frames —
  all frames are submitted BEFORE any is waited on, so one client's
  pipeline fills batches alongside other streams (cross-stream
  coalescing).  Response is NDJSON in submit order, each line
  ``{"status", "stream_id", "seq", "skipped", "detections",
  "queue_wait_ms"}``; per-frame statuses mirror ``/predict`` (400/503/
  504), plus 409 for a stale ``seq``.  The HTTP envelope is 200 as long
  as the body parsed.
* ``GET /healthz`` — liveness: 200 once the engine thread is up (a
  warming or draining replica still answers — backward-compatible).
* ``GET /readyz`` — readiness: 200 only once warmup has registered every
  program AND admissions are open (not draining for a weight swap);
  503 otherwise.  What the replica supervisor and smoke scripts gate
  routing on — liveness and readiness are deliberately distinct.
* ``POST /admin/reload`` — replica-local checkpoint hot-swap (only when
  the server was built with a ``reloader`` callback; 404 otherwise).
  Body is a reload target doc; 200 → new generation live, 409 → load or
  canary failure, previous weights restored.
* ``GET /metrics`` — engine counters + queue state as JSON (with the
  cumulative clock of every stage under ``"stages"`` — this module's own
  are ``frontend/read``, ``frontend/decode`` and ``frontend/reply``, one
  observation a ``/predict`` request and a profiler annotation each, booked
  into the engine the request resolved to); with
  ``Accept: text/plain`` or ``?format=prom``, Prometheus text exposition
  instead — rendered by ``telemetry/obs.py`` from the same registry the
  ``--obs-port`` server scrapes (one metrics path, not two).

Everything here is stdlib (``http.server`` + ``ThreadingHTTPServer``):
request threads do the image prep in ``engine.submit`` concurrently, which
is precisely what fills batches — a single-threaded frontend would
serialize arrivals and the batcher would only ever see singletons.
"""

from __future__ import annotations

import base64
import json
import os
import socket
import sys
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

import numpy as np

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.serve.engine import (DeadlineExceededError, RejectedError,
                                      ServeEngine)
from mx_rcnn_tpu.serve.stream import StaleSeqError, StreamManager
from mx_rcnn_tpu.telemetry import tracectx
from mx_rcnn_tpu.telemetry.obs import (PROM_CONTENT_TYPE, pool_prometheus,
                                       serve_prometheus)
from mx_rcnn_tpu.telemetry.tracectx import TRACE_HEADER, TraceContext

# result-wait ceiling for one HTTP request; the engine's own per-request
# deadline (default ServeOptions.deadline_ms) fires long before this —
# the ceiling only bounds a wedged dispatcher so handler threads can't
# accumulate forever
WAIT_TIMEOUT_S = 600.0


def decode_image_payload(doc: dict) -> np.ndarray:
    """Request JSON → (H, W, 3) uint8 RGB array.  Raises ValueError on a
    malformed payload (the handler's 400)."""
    if "pixels" in doc:
        img = np.asarray(doc["pixels"], np.uint8)
    elif "data" in doc:
        shape = doc.get("shape")
        if (not isinstance(shape, (list, tuple)) or len(shape) != 3
                or shape[2] != 3):
            raise ValueError(f"'shape' must be [h, w, 3], got {shape!r}")
        raw = base64.b64decode(doc["data"], validate=True)
        h, w, c = (int(x) for x in shape)
        if len(raw) != h * w * c:
            raise ValueError(f"'data' holds {len(raw)} bytes, shape "
                             f"{shape} needs {h * w * c}")
        img = np.frombuffer(raw, np.uint8).reshape(h, w, c)
    else:
        raise ValueError("payload needs 'data'+'shape' (base64 raw RGB "
                         "bytes) or 'pixels' (nested lists)")
    if img.ndim != 3 or img.shape[2] != 3:
        raise ValueError(f"expected (H, W, 3), got {img.shape}")
    return img


def encode_image_payload(img: np.ndarray) -> dict:
    """The client half of the contract (loadgen, tests)."""
    img = np.ascontiguousarray(img, np.uint8)
    return {"shape": list(img.shape),
            "data": base64.b64encode(img.tobytes()).decode("ascii")}


def _predict_doc(engine: ServeEngine, doc: dict, img,
                 trace, cascade=None, model_id=None) -> tuple:
    """The submit+wait core of one predict request — trace-agnostic, so
    the traced and untraced paths produce IDENTICAL response docs (the
    tracing-off byte-parity contract).  With a ``cascade`` router the
    submit routes through it instead of the engine and the 200 response
    grows a ``"cascade"`` provenance field (which model answered and
    why); cascade-off responses stay byte-for-byte."""
    try:
        if cascade is not None:
            fut = cascade.submit(img, deadline_ms=doc.get("deadline_ms"),
                                 trace=trace, model_id=model_id)
        else:
            fut = engine.submit(img, deadline_ms=doc.get("deadline_ms"),
                                trace=trace)
        dets = fut.result(timeout=WAIT_TIMEOUT_S)
    except RejectedError as e:
        return 503, {"error": str(e)}
    except DeadlineExceededError as e:
        return 504, {"error": str(e)}
    except TimeoutError as e:
        return 504, {"error": str(e)}
    except Exception as e:  # noqa: BLE001 — surface as a 500, keep serving
        logger.exception("predict failed")
        return 500, {"error": f"{type(e).__name__}: {e}"}
    qms = (fut.queue_wait_s or 0.0) * 1e3
    resp = {"detections": dets, "queue_wait_ms": round(qms, 3)}
    if cascade is not None:
        resp["cascade"] = fut.provenance()
    return 200, resp


def handle_request_doc(engine: ServeEngine, doc: dict,
                       trace_header: Optional[str] = None,
                       cascade=None, model_id=None) -> tuple:
    """One predict request → (http_status, response_doc).  Shared by all
    three transports so their status semantics cannot drift.

    Trace context comes from the forwarded ``X-Mxr-Trace`` header (the
    router's chain wins — it carries the parent span) or the ``"trace"``
    doc field (a client-minted bare trace id); with tracing enabled and
    neither present, one is minted here — the frontend is the root of
    the hop tree either way.  The trace id is echoed back as a
    ``"trace"`` response key ONLY when the client sent one or tracing is
    on, so a tracing-off ``/predict`` stays byte-for-byte."""
    try:
        with telemetry.stage("frontend/decode",
                             engine.hists["frontend/decode"]):
            img = decode_image_payload(doc)
    except (ValueError, TypeError, KeyError) as e:
        return 400, {"error": str(e)}
    tracer = tracectx.get()
    raw = trace_header or doc.get("trace")
    if not tracer.enabled:
        status, resp = _predict_doc(engine, doc, img, None,
                                    cascade=cascade, model_id=model_id)
        if raw:
            # propagation without recording: a client that minted an id
            # still gets it echoed so cross-host correlation never
            # depends on which members have tracing on
            resp["trace"] = str(raw).split("-", 1)[0]
        return status, resp
    ctx = (TraceContext.parse(raw) if raw else None) or tracer.mint()
    with tracer.span(ctx, "frontend/predict") as sp:
        status, resp = _predict_doc(engine, doc, img, sp.ctx,
                                    cascade=cascade, model_id=model_id)
        sp.set(status=status)
    resp["trace"] = ctx.trace_id
    return status, resp


def submit_stream_frame(stream: StreamManager, doc: dict,
                        trace_header: Optional[str] = None) -> tuple:
    """Validate + submit one stream frame WITHOUT waiting — the submit
    half of the pipelined ``/stream`` handler.  Returns
    ``(None, None, FrameResult)`` on acceptance or
    ``(status, error_doc, None)`` on submit-side failure.

    Tracing mirrors ``/predict``: per-frame ``"trace"`` doc field (or the
    body's forwarded header) is accepted, else one is minted when tracing
    is on; a ``frontend/frame`` span covers the gate+submit and parents
    the stream-gate / engine spans below it."""
    sid, seq = doc.get("stream_id"), doc.get("seq")
    if not isinstance(sid, str) or not sid:
        return 400, {"error": "frame needs a non-empty string "
                              "'stream_id'"}, None
    if not isinstance(seq, int) or isinstance(seq, bool):
        return 400, {"error": "frame needs an integer 'seq'",
                     "stream_id": sid}, None
    try:
        img = decode_image_payload(doc)
    except (ValueError, TypeError, KeyError) as e:
        return 400, {"error": str(e), "stream_id": sid, "seq": seq}, None
    tracer = tracectx.get()
    sp = tracectx.NULL_SPAN
    if tracer.enabled:
        raw = doc.get("trace") or trace_header
        ctx = (TraceContext.parse(raw) if raw else None) or tracer.mint()
        sp = tracer.span(ctx, "frontend/frame", stream=sid, seq=seq)
    try:
        with sp:
            res = stream.submit_frame(sid, seq, img,
                                      deadline_ms=doc.get("deadline_ms"),
                                      trace=sp.ctx)
    except StaleSeqError as e:
        return 409, {"error": str(e), "stream_id": sid, "seq": seq}, None
    except RejectedError as e:
        return 503, {"error": str(e), "stream_id": sid, "seq": seq}, None
    except Exception as e:  # noqa: BLE001 — surface as a 500, keep serving
        logger.exception("stream submit failed")
        return 500, {"error": f"{type(e).__name__}: {e}",
                     "stream_id": sid, "seq": seq}, None
    return None, None, res


def resolve_stream_frame(res) -> tuple:
    """The wait half: one accepted :class:`FrameResult` →
    ``(status, response_doc)`` with ``/predict``'s status semantics."""
    try:
        dets = res.result(timeout=WAIT_TIMEOUT_S)
    except RejectedError as e:
        return 503, {"error": str(e), "stream_id": res.stream_id,
                     "seq": res.seq}
    except (DeadlineExceededError, TimeoutError) as e:
        return 504, {"error": str(e), "stream_id": res.stream_id,
                     "seq": res.seq}
    except Exception as e:  # noqa: BLE001
        logger.exception("stream frame failed")
        return 500, {"error": f"{type(e).__name__}: {e}",
                     "stream_id": res.stream_id, "seq": res.seq}
    out = {"stream_id": res.stream_id, "seq": res.seq,
           "skipped": res.skipped, "detections": dets,
           "queue_wait_ms": round((res.queue_wait_s or 0.0) * 1e3, 3)}
    if res.delta is not None:
        out["delta"] = round(res.delta, 4)
    # cascade provenance, only for cascade-routed streams — non-cascade
    # frames (and pre-cascade fakes in tests) stay byte-for-byte
    prov = getattr(res, "cascade", None)
    prov = prov() if callable(prov) else None
    if prov is not None:
        out["cascade"] = prov
    return 200, out


def handle_stream_doc(stream: StreamManager, doc: dict,
                      trace_header: Optional[str] = None) -> tuple:
    """One frame, submit + wait → (status, response_doc).  The stdio
    transport's unit; HTTP goes through :func:`handle_stream_lines` to
    pipeline multi-frame bodies."""
    status, err, res = submit_stream_frame(stream, doc,
                                           trace_header=trace_header)
    if res is None:
        return status, err
    return resolve_stream_frame(res)


def handle_stream_lines(stream: StreamManager, lines,
                        trace_header: Optional[str] = None) -> list:
    """NDJSON body → list of (status, doc) replies in input order.
    Submits EVERY frame before resolving any, so a single connection's
    burst coalesces into shared batches instead of serializing."""
    staged = []
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            doc = json.loads(line)
        except json.JSONDecodeError as e:
            staged.append((400, {"error": f"bad JSON line: {e}"}, None))
            continue
        staged.append(submit_stream_frame(stream, doc,
                                          trace_header=trace_header))
    return [(status, err) if res is None else resolve_stream_frame(res)
            for status, err, res in staged]


def query_model(query: str) -> Optional[str]:
    """Extract ``model=...`` from a raw query string (None if absent)."""
    for part in query.split("&"):
        k, _, v = part.partition("=")
        if k == "model" and v:
            return v
    return None


def query_param(query: str, key: str) -> Optional[str]:
    """Extract ``key=...`` from a raw query string (None if absent) —
    URL-decoded just enough for metric names (``%2F`` → ``/``)."""
    for part in query.split("&"):
        k, _, v = part.partition("=")
        if k == key and v:
            return v.replace("%2F", "/").replace("%2f", "/")
    return None


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    engine: ServeEngine = None  # set by make_server subclassing
    stream: Optional[StreamManager] = None  # enables POST /stream
    pool = None          # optional ModelPool: enables ?model=... routing
    streams = None       # pool mode: {model_id: StreamManager}
    cascade = None       # optional CascadeRouter: /predict rides it
    reloader = None      # optional callback(doc) -> (status, doc)
    request_hook = None  # optional callback(status) after each /predict
    gate = None          # optional callback() before any handling
    net_faults = None    # optional NetFaults: intercept(path, handler)
    watch = None         # optional Watchtower: /alerts + /history + Prom

    def _resolve_engine(self, query: str, doc: Optional[dict] = None):
        """``?model=...`` (or a ``"model"`` field in the request doc) →
        ``(engine, None)`` or ``(None, (status, error_doc))``.  Without a
        pool, any explicit model selector is a 404 (multi-model routing
        is opt-in via ``--models``); with one, the id resolves to that
        model's own engine — its bucket set, programs, AOT subtree."""
        mid = query_model(query) if query else None
        if mid is None and doc is not None:
            m = doc.get("model")
            if isinstance(m, str) and m:
                mid = m
        if self.pool is None:
            if mid is not None:
                return None, (404, {"error": f"model routing not enabled "
                                             f"(requested {mid!r}; start "
                                             f"with --models)"})
            return self.engine, None
        try:
            return self.pool.engine_for(mid), None
        except KeyError as e:
            return None, (404, {"error": str(e.args[0]) if e.args
                                else str(e)})

    # -- plumbing --------------------------------------------------------

    def log_message(self, fmt, *args):  # route through our logger
        logger.debug("serve http: " + fmt, *args)

    def address_string(self):  # AF_UNIX peers have no (host, port)
        if isinstance(self.client_address, (bytes, str)):
            return "unix"
        return super().address_string()

    def _reply(self, status: int, doc: dict):
        self._reply_raw(status, json.dumps(doc).encode(),
                        "application/json")

    def _reply_raw(self, status: int, body: bytes, ctype: str):
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    # -- endpoints -------------------------------------------------------

    def do_GET(self):
        if self.gate is not None:
            self.gate()
        if self.net_faults is not None and \
                self.net_faults.intercept(self.path, self):
            return
        path, _, query = self.path.partition("?")
        if path == "/healthz":
            if self.pool is not None:
                self._reply(200, {"status": "ok",
                                  "models": self.pool.model_ids(),
                                  "queue_depth": sum(
                                      self.pool.engine_for(m).queue_depth()
                                      for m in self.pool.model_ids())})
            else:
                self._reply(200, {"status": "ok",
                                  "queue_depth":
                                      self.engine.queue_depth()})
        elif path == "/readyz":
            doc = (self.pool.readiness() if self.pool is not None
                   else self.engine.readiness())
            self._reply(200 if doc["ready"] else 503, doc)
        elif path == "/metrics":
            # content negotiation: JSON stays the default for existing
            # callers; Prometheus scrapers ask via Accept or ?format=prom
            accept = self.headers.get("Accept", "")
            if "format=prom" in query or "text/plain" in accept:
                text = (pool_prometheus(self.pool, watch=self.watch)
                        if self.pool is not None
                        else serve_prometheus(self.engine,
                                              watch=self.watch))
                self._reply_raw(200, text.encode(), PROM_CONTENT_TYPE)
            elif self.pool is not None:
                doc = self.pool.metrics()
                if self.watch is not None:
                    doc["watch"] = self.watch.state()
                self._reply(200, doc)
            else:
                doc = self.engine.metrics()
                if self.watch is not None:
                    doc["watch"] = self.watch.state()
                self._reply(200, doc)
        elif path == "/alerts" and self.watch is not None:
            self._reply(200, self.watch.alerts_doc())
        elif path == "/history" and self.watch is not None:
            metric = query_param(query, "metric")
            if not metric:
                self._reply(400, {"error": "need ?metric=NAME"})
                return
            try:
                window = float(query_param(query, "window") or 300.0)
            except ValueError:
                self._reply(400, {"error": "window must be a number "
                                           "of seconds"})
                return
            self._reply(200, self.watch.history_doc(metric, window))
        else:
            # /alerts and /history 404 when the watchtower is off —
            # byte-identical to the pre-watch unknown-path reply
            self._reply(404, {"error": f"no route {self.path}"})

    def do_POST(self):
        if self.gate is not None:
            self.gate()
        if self.net_faults is not None and \
                self.net_faults.intercept(self.path, self):
            return
        # query split mirrors do_GET: /predict?model=... must route, and
        # a bare single-model boot keeps 404-ing unknown query'd paths
        # through the explicit model-routing error below
        path, _, query = self.path.partition("?")
        if path not in ("/predict", "/admin/reload", "/stream"):
            self._reply(404, {"error": f"no route {self.path}"})
            return
        if path == "/stream":
            # pool mode: ?model=... picks that model's StreamManager (the
            # /predict routing twin); frames inside one body share it
            stream = self.stream
            if self.pool is not None:
                mid = query_model(query) or self.pool.default_model
                stream = (self.streams or {}).get(mid)
                if stream is None and mid not in self.pool.model_ids():
                    self._reply(404, {"error": f"unknown model {mid!r} "
                                      f"(have {self.pool.model_ids()})"})
                    return
            if stream is None:
                self._reply(404, {"error": "streaming not enabled "
                                           "(start with --stream)"})
                return
            try:
                length = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(length)
            except ValueError as e:
                self._reply(400, {"error": f"bad Content-Length: {e}"})
                return
            replies = handle_stream_lines(
                stream, body.decode("utf-8", "replace").splitlines(),
                trace_header=self.headers.get(TRACE_HEADER))
            payload = "".join(json.dumps({"status": s, **d}) + "\n"
                              for s, d in replies)
            self._reply_raw(200, payload.encode(), "application/x-ndjson")
            return
        try:
            # observed below, once the body has said which engine it is for
            with telemetry.stage("frontend/read") as read:
                length = int(self.headers.get("Content-Length", 0))
                doc = json.loads(self.rfile.read(length) or b"{}")
        except (ValueError, json.JSONDecodeError) as e:
            self._reply(400, {"error": f"bad JSON body: {e}"})
            return
        if path == "/admin/reload":
            if self.reloader is None:
                self._reply(404, {"error": "no reloader configured"})
                return
            self._reply(*self.reloader(doc))
            return
        engine, err = self._resolve_engine(query, doc)
        if engine is None:
            self._reply(*err)
            if self.request_hook is not None:
                self.request_hook(err[0])
            return
        mid = None
        if self.cascade is not None:
            # the router routes by model IDENTITY (addressed big model /
            # fidelity pin / bypass / gate), so it needs the id, not the
            # engine _resolve_engine already validated
            mid = query_model(query) if query else None
            if mid is None:
                m = doc.get("model")
                if isinstance(m, str) and m:
                    mid = m
        read.book(engine.hists["frontend/read"])
        status, resp = handle_request_doc(
            engine, doc, trace_header=self.headers.get(TRACE_HEADER),
            cascade=self.cascade, model_id=mid)
        with telemetry.stage("frontend/reply",
                             engine.hists["frontend/reply"]):
            self._reply(status, resp)
        if self.request_hook is not None:
            self.request_hook(status)


class _TCPHTTPServer(ThreadingHTTPServer):
    # the stdlib default listen backlog (5) drops connections under the
    # very bursts the engine's backpressure exists to answer with 503s;
    # admission control is the engine's job, not the kernel's
    request_queue_size = 128


class _UnixHTTPServer(_TCPHTTPServer):
    address_family = socket.AF_UNIX

    def server_bind(self):
        # stale socket files from a killed process block bind
        if os.path.exists(self.server_address):
            os.unlink(self.server_address)
        super().server_bind()

    def client_address_string(self):
        return "unix"


def make_server(engine: ServeEngine, port: Optional[int] = None,
                host: str = "127.0.0.1",
                unix_socket: Optional[str] = None,
                reloader=None, request_hook=None, gate=None,
                net_faults=None, stream: Optional[StreamManager] = None,
                pool=None, streams: Optional[dict] = None, cascade=None,
                watch=None):
    """Build (not start) the HTTP server — exactly one of ``port`` /
    ``unix_socket``.  Caller owns ``serve_forever``/``shutdown``.

    ``reloader`` enables ``POST /admin/reload`` (the replica hot-swap
    endpoint); ``request_hook(status)`` fires after each ``/predict``
    reply and ``gate()`` before any handling — the chaos harness's
    kill-after-N / hang injection points.  ``net_faults`` (an object
    with ``intercept(path, handler) -> bool``) sits below both and can
    blackhole, delay, or reset the connection — the fabric chaos
    harness's network-layer injection point.

    ``pool`` (a :class:`~mx_rcnn_tpu.serve.pool.ModelPool`) turns on
    multi-model routing: ``?model=...`` on ``/predict``/``/stream``
    resolves to that model's engine / StreamManager (``streams``:
    model_id → manager), ``/metrics`` reports the whole fleet, and
    ``/readyz`` requires every model warm.  ``engine`` stays the default
    model's engine so single-model callers are untouched.

    ``watch`` (a :class:`~mx_rcnn_tpu.telemetry.watch.Watchtower`)
    enables ``GET /alerts`` and ``GET /history?metric=&window=`` plus
    the ``watch`` pane / ``mxr_alert_state`` family on ``/metrics``;
    None keeps every response byte-identical to the watch-less server
    (both routes 404)."""
    if (port is None) == (unix_socket is None):
        raise ValueError("pass exactly one of port / unix_socket")

    class Handler(_Handler):
        pass

    Handler.engine = engine
    Handler.stream = stream  # a StreamManager enables POST /stream
    Handler.pool = pool
    Handler.streams = streams
    Handler.cascade = cascade  # a CascadeRouter: /predict rides the gate
    # staticmethod: a plain function stored on the class would otherwise
    # bind as a method and receive the handler as a bogus first argument
    Handler.reloader = staticmethod(reloader) if reloader else None
    Handler.request_hook = (staticmethod(request_hook)
                            if request_hook else None)
    Handler.gate = staticmethod(gate) if gate else None
    Handler.net_faults = net_faults
    Handler.watch = watch  # a Watchtower enables /alerts + /history
    if unix_socket is not None:
        return _UnixHTTPServer(unix_socket, Handler)
    return _TCPHTTPServer((host, port), Handler)


def unix_http_request_raw(sock_path: str, method: str, path: str,
                          body: Optional[bytes] = None,
                          timeout: float = 60.0,
                          headers: Optional[dict] = None) -> tuple:
    """Byte-level HTTP over a Unix socket → (status, body_bytes, ctype).
    The router's forwarding primitive: request bodies pass through
    verbatim (no decode→re-encode of base64 image payloads on the
    hot path).  Raises ``OSError`` family on transport failure — a dead
    or hung replica — which is the retry-on-alternate trigger."""
    import http.client

    class Conn(http.client.HTTPConnection):
        def __init__(self):
            super().__init__("localhost", timeout=timeout)

        def connect(self):
            self.sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            self.sock.settimeout(timeout)
            self.sock.connect(sock_path)

    conn = Conn()
    try:
        hdrs = dict(headers or {})
        if body:
            hdrs.setdefault("Content-Type", "application/json")
        conn.request(method, path, body=body, headers=hdrs)
        resp = conn.getresponse()
        return (resp.status, resp.read(),
                resp.getheader("Content-Type") or "")
    finally:
        conn.close()


def unix_http_request(sock_path: str, method: str, path: str,
                      doc: Optional[dict] = None,
                      timeout: float = 60.0,
                      headers: Optional[dict] = None) -> tuple:
    """Minimal HTTP client over a Unix socket → (status, response_doc).
    The test/loadgen counterpart of ``make_server(unix_socket=...)``.
    JSON responses come back parsed; anything else (the Prometheus text
    negotiated via ``headers={"Accept": "text/plain"}``) as str."""
    body = json.dumps(doc).encode() if doc is not None else None
    status, raw, ctype = unix_http_request_raw(
        sock_path, method, path, body=body, timeout=timeout,
        headers=headers)
    if "json" in ctype:
        return status, json.loads(raw)
    return status, raw.decode()


def tcp_http_request_raw(host: str, port: int, method: str, path: str,
                         body: Optional[bytes] = None,
                         timeout: float = 60.0,
                         headers: Optional[dict] = None) -> tuple:
    """Byte-level HTTP over TCP → (status, body_bytes, ctype): the
    fabric router's forwarding primitive for remote members — the
    cross-host twin of :func:`unix_http_request_raw`, with the same
    pass-through-bytes and raise-on-transport-failure contract."""
    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        hdrs = dict(headers or {})
        if body:
            hdrs.setdefault("Content-Type", "application/json")
        conn.request(method, path, body=body, headers=hdrs)
        resp = conn.getresponse()
        return (resp.status, resp.read(),
                resp.getheader("Content-Type") or "")
    finally:
        conn.close()


def tcp_http_request(host: str, port: int, method: str, path: str,
                     doc: Optional[dict] = None, timeout: float = 60.0,
                     headers: Optional[dict] = None) -> tuple:
    """JSON-level HTTP over TCP → (status, response_doc) — the client
    for fabric probes, ``--join`` registration, and the smoke scripts."""
    body = json.dumps(doc).encode() if doc is not None else None
    status, raw, ctype = tcp_http_request_raw(
        host, port, method, path, body=body, timeout=timeout,
        headers=headers)
    if "json" in ctype:
        return status, json.loads(raw)
    return status, raw.decode()


def parse_address(address: str) -> tuple:
    """``host:port`` → ("tcp", host, port); a filesystem path (optional
    ``unix:`` prefix) → ("unix", path, None).  The fabric's one address
    grammar for pool files, ``--join``, and ``/admin/register``."""
    address = address.strip()
    if address.startswith("unix:"):
        return "unix", address[5:], None
    if "/" in address:
        return "unix", address, None
    host, _, port = address.rpartition(":")
    if not host or not port.isdigit():
        raise ValueError(f"address must be HOST:PORT or a unix socket "
                         f"path, got {address!r}")
    return "tcp", host, int(port)


def address_request_raw(address: str, method: str, path: str,
                        body: Optional[bytes] = None,
                        timeout: float = 60.0,
                        headers: Optional[dict] = None) -> tuple:
    """Transport-agnostic byte-level request: dispatches on
    :func:`parse_address` so fabric members are addressed identically
    whether they live across the network or across a fork."""
    scheme, host, port = parse_address(address)
    if scheme == "unix":
        return unix_http_request_raw(host, method, path, body=body,
                                     timeout=timeout, headers=headers)
    return tcp_http_request_raw(host, port, method, path, body=body,
                                timeout=timeout, headers=headers)


def address_request(address: str, method: str, path: str,
                    doc: Optional[dict] = None, timeout: float = 60.0,
                    headers: Optional[dict] = None) -> tuple:
    """JSON twin of :func:`address_request_raw`."""
    body = json.dumps(doc).encode() if doc is not None else None
    status, raw, ctype = address_request_raw(
        address, method, path, body=body, timeout=timeout,
        headers=headers)
    if "json" in ctype:
        return status, json.loads(raw)
    return status, raw.decode()


def run_stdio(engine: ServeEngine, inp=None, out=None):
    """Newline-delimited JSON over stdin/stdout: each input line is a
    predict payload, each output line ``{"status": N, ...response}``.
    Returns on EOF."""
    inp = inp if inp is not None else sys.stdin
    out = out if out is not None else sys.stdout
    for line in inp:
        line = line.strip()
        if not line:
            continue
        try:
            doc = json.loads(line)
        except json.JSONDecodeError as e:
            status, resp = 400, {"error": f"bad JSON line: {e}"}
        else:
            status, resp = handle_request_doc(engine, doc)
        out.write(json.dumps({"status": status, **resp}) + "\n")
        out.flush()


def run_stream_stdio(stream: StreamManager, inp=None, out=None):
    """Stream twin of :func:`run_stdio`: each input line is a frame doc
    (predict payload + ``stream_id``/``seq``), each output line
    ``{"status": N, ...}`` — the pipe-based stream harness the contract
    tests drive without a socket.  Returns on EOF."""
    inp = inp if inp is not None else sys.stdin
    out = out if out is not None else sys.stdout
    for line in inp:
        line = line.strip()
        if not line:
            continue
        try:
            doc = json.loads(line)
        except json.JSONDecodeError as e:
            status, resp = 400, {"error": f"bad JSON line: {e}"}
        else:
            status, resp = handle_stream_doc(stream, doc)
        out.write(json.dumps({"status": status, **resp}) + "\n")
        out.flush()
