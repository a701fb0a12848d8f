#!/usr/bin/env python
"""Open-loop HTTP load generator for serve.py — latency under load.

  python scripts/loadgen.py --host 127.0.0.1 --port 8321 --n 64 --rate 20
  python scripts/loadgen.py --unix-socket /tmp/serve.sock --n 32 --rate 0
  python scripts/loadgen.py --port 8321 --scenario steady --scenario bursty \
      --n 64 --rate 40 --report /tmp/slo.json

Open-loop: request k is FIRED at its scheduled instant regardless of
whether earlier responses came back (each request gets its own thread),
so a slow server accumulates in-flight work and the latency distribution
shows it — closed-loop generators that wait for responses throttle
themselves to the server's pace and hide exactly the queueing behavior
this exists to measure (the coordinated-omission trap).  ``--rate 0``
fires everything at once (burst mode: what backpressure tests want).

Bodies are mixed-size random uint8 images — half landscape, half
portrait, dimensions jittered per request (seeded) — so the server
exercises both orientation buckets and real ``resize_to_bucket`` work.

Scenario profiles (``--scenario``, repeatable — the SLO gate's workload
vocabulary):

* ``steady``   — uniform arrivals at ``--rate`` (the baseline SLO).
* ``bursty``   — same average rate, but arrivals clump into bursts of
  ``--burst`` fired back-to-back: the workload that exposes queue bloat
  and exercises the SLO controller's shed valve.
* ``size-mix`` — steady arrivals, adversarial size jitter (full range
  down to tiny images, random orientation flips): stresses per-bucket
  routing and batch fill.

Without ``--scenario`` one anonymous steady run prints exactly ONE JSON
line (the PR-3 contract):

  {"requests": N, "status": {"200": k, "503": m, ...}, "p50_ms": ...,
   "p99_ms": ..., "error_rate": ..., "mean_queue_wait_ms": ...,
   "imgs_per_sec": ..., "wall_s": ...}

With scenarios, one such line prints per scenario (prefixed by its name
under ``"scenario"``), and ``--report PATH`` additionally writes the
machine-readable SLO report ``scripts/perf_gate.py`` gates:

  {"schema": "mxr_slo_report", "version": 1,
   "scenarios": [{"name": "steady", "requests": ..., "status": {...},
                  "p50_ms": ..., "p99_ms": ..., "error_rate": ...,
                  "availability": ..., "time_to_recover_s": ...,
                  "imgs_per_sec": ..., "wall_s": ...}, ...]}

Failover metrics (ISSUE 8): ``availability`` is the 2xx fraction over
NON-SHED submits (503s are deliberate backpressure, not unavailability);
``time_to_recover_s`` is the gap from the first hard failure (5xx or
transport error) to the next 2xx COMPLETION after it, null when the run
never hard-failed.

latency percentiles are over 2xx responses (client-observed, including
queue wait + forward + post-process + transport); ``imgs_per_sec`` is
2xx responses over the wall from first fire to last response;
``error_rate`` is the non-2xx fraction.  With ``--assert-2xx`` the exit
code is 1 unless every response was 2xx, and the failure line on stderr
names each offending status and its count.  Stdlib + numpy on its own
paths; importing ``mx_rcnn_tpu.serve.frontend`` for the payload codec does
put jax into ``sys.modules``, but no back end is ever initialised (pinned
by tests/test_chip_smoke.py) — safe on a machine with no accelerator, and
beside a server process that holds the chip.

Fabric mode (ISSUE 12): with ``--fabric`` the TCP target is a fabric
router (serve.py --fabric) — the router's ``/metrics`` per-member
request counters are snapshotted around every scenario and each output
line/report row gains ``member_share``, the fraction of the scenario's
routed requests each member served (the routing-balance evidence
script/fabric_smoke.sh and the FABRIC_r*.json gate read), plus
``fabric_members``, the live member count at scenario end.

Capture check (ISSUE 13): with ``--capture-check`` the target's
``/metrics`` flywheel ``captured`` counter is snapshotted around the
whole run and the delta must match ``2xx submits / sample_every``
within ``--capture-tolerance`` (exit 1 otherwise) — the smoke-script
guard against silent capture loss.

Stream mode (ISSUE 14): ``--streams N`` switches to the camera model —
N concurrent streams, each a CLOSED loop at ``--fps`` over ONE
persistent keep-alive connection to ``POST /stream``, frames sequenced
per stream.  Closed-loop is deliberate here (the opposite of the
request mode above): a camera cannot fire frame k+1 before frame k's
slot, so a slow server shows up as ``frames_dropped`` (scheduled slots
abandoned because the sender was more than one frame interval late),
not as unbounded in-flight pileup.  ``--motion`` picks the per-frame
pixel dynamics (repeatable — one scenario per profile):

* ``static``    — fixed scene + per-frame sensor noise on ~5% of pixels:
  the skip gate's best case.
* ``pan``       — the scene translates a few pixels per frame: every
  frame differs everywhere, the gate must NOT skip.
* ``scene-cut`` — a new random scene every ``--cut-every`` frames,
  static between cuts: exercises both gate edges.

Each scenario prints one JSON line and contributes one row to the
``--report`` doc, which in stream mode uses schema ``mxr_stream_report``
(per-stream p99 list, max-over-streams ``p99_ms``, ``frames_dropped``,
client-observed ``skip_fraction`` from response ``skipped`` flags, and
``dispatches_per_frame`` diffed from the server's ``/metrics`` engine
counters).  ``--skip-floor``/``--p99-ceiling-ms`` attach the
``perf_gate.py`` floor/ceiling fields to the rows the gate scores.

Multi-model mode (ISSUE 15): ``--models a=0.7,b=0.3`` targets a model
pool (serve.py --models): every request carries a ``"model"`` field
drawn from the given mix (seeded), and two scenarios run —

* ``mixed`` — open-loop steady arrivals, models interleaved per the
  mix: the aggregate-throughput workload.
* ``burst`` — the non-burst models keep their steady share of
  ``--rate`` while ``--burst-model`` (default: the first in the mix)
  fires ALL its requests back-to-back mid-run: the tenant-isolation
  workload — the sibling models' p99 under the burst is what the
  MULTIMODEL gate's isolation ceiling scores.

Each scenario prints one JSON line with per-model ``p50_ms``/
``p99_ms``/``availability``/``error_rate`` blocks under ``"models"``
alongside the aggregate fields, and ``--report`` writes schema
``mxr_multimodel_report``.  ``--throughput-floor`` attaches the
aggregate ``imgs_per_sec`` floor to the mixed row;
``--p99-ceiling-ms`` attaches the isolation ceiling the gate enforces
on every NON-burst model in the burst row.

Cascade mode (ISSUE 19): ``--cascade`` targets a pool serving with a
cascade router (serve.py --cascade small:big — the pair is discovered
from the target's ``/metrics`` cascade section, no flags to repeat).
Two scenarios run over IDENTICAL seeded payloads:

* ``big_only`` — every request addressed straight at the big model
  (``"model": <big>``, bypassing the gate): the throughput baseline
  and the agreement reference.
* ``cascade``  — default routing through the confidence gate; response
  docs are retained so the ``cascade`` provenance field yields the
  client-observed ``escalation_rate`` and the per-class
  (``answered_small`` vs ``escalated``) latency split, and the
  ``detections`` yield ``agreement`` — mean ``detection_agreement``
  (the PR-17 promotion-gate metric) against the big-only answers for
  the same images.

``--report`` writes schema ``mxr_cascade_report``.  The gate pins ride
the cascade row: ``speedup_vs_big`` (cascade imgs/s over big-only
imgs/s, floored by ``--speedup-floor``, default 1.0 — the cascade must
not LOSE to always-big), ``--agreement-floor`` (mean agreement floor),
and ``--throughput-floor`` (absolute imgs/s floor) — what
``perf_gate.py`` scores on CASCADE_r*.json.

``--watch-check`` (ISSUE 20, script/watch_smoke.sh): scrape the
target's ``/alerts`` (a serve.py --watch process) after the run and
assert the alert set — no ``--watch-expect`` means NOTHING may have
fired (the clean-traffic contract); each ``--watch-expect NAME`` must
have fired, and nothing outside the expected set may still be firing.
Each scenario summary gains an ``alerts`` block either way.
"""

import argparse
import http.client
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mx_rcnn_tpu.serve.frontend import (encode_image_payload,  # noqa: E402
                                        unix_http_request)

REPORT_SCHEMA = "mxr_slo_report"
STREAM_REPORT_SCHEMA = "mxr_stream_report"
MULTIMODEL_REPORT_SCHEMA = "mxr_multimodel_report"
AUTOSCALE_REPORT_SCHEMA = "mxr_autoscale_report"
CASCADE_REPORT_SCHEMA = "mxr_cascade_report"
REPORT_VERSION = 1
SCENARIOS = ("steady", "bursty", "size-mix")
PROFILES = ("diurnal", "flashcrowd")

# time-varying open-loop profiles (ISSUE 18): per segment a fraction of
# --n fired at a multiple of --rate.  diurnal = piecewise ramp up to a
# peak and back (the daily traffic curve, compressed); flashcrowd = a
# steady baseline with a near-back-to-back spike in the middle — the
# shape a predictive autoscaler must beat
PROFILE_SEGMENTS = {
    "diurnal": ((0.2, 0.4), (0.2, 0.8), (0.2, 1.6), (0.2, 0.8),
                (0.2, 0.4)),
    "flashcrowd": ((0.4, 0.5), (0.4, 8.0), (0.2, 0.5)),
}
MOTIONS = ("static", "pan", "scene-cut")


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--unix-socket", default="", dest="unix_socket",
                    help="target a Unix-socket server instead of TCP")
    ap.add_argument("--n", type=int, default=32,
                    help="requests to fire (per scenario)")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="average arrival rate, req/s (0 = fire all at "
                         "once)")
    ap.add_argument("--scenario", action="append", choices=SCENARIOS,
                    dest="scenarios", default=None,
                    help="run this named profile (repeatable; omit for "
                         "one anonymous steady run)")
    ap.add_argument("--burst", type=int, default=8,
                    help="bursty scenario: requests per burst (fired "
                         "back-to-back; bursts spaced to keep --rate on "
                         "average)")
    ap.add_argument("--profile", default="", choices=("",) + PROFILES,
                    help="time-varying open-loop rate schedule (ISSUE "
                         "18): diurnal = piecewise ramp up/down around "
                         "--rate, flashcrowd = baseline + spike; the "
                         "segment schedule is emitted into the report "
                         "row for reproducibility, and with --report "
                         "the doc becomes an mxr_autoscale_report")
    ap.add_argument("--fleet-poll-s", type=float, default=0.3,
                    dest="fleet_poll_s",
                    help="--profile + --fabric: sample the router's "
                         "ready-member count this often during the run "
                         "(feeds time_to_scale_s)")
    ap.add_argument("--scale-floor", type=float, default=0.0,
                    dest="scale_floor",
                    help="autoscale report: perf_gate floor on peak "
                         "minus starting ready-member count (0 = no "
                         "row)")
    ap.add_argument("--time-to-scale-ceiling-s", type=float, default=0.0,
                    dest="time_to_scale_ceiling_s",
                    help="autoscale report: perf_gate ceiling on "
                         "time_to_scale_s (0 = trend-only row)")
    ap.add_argument("--report", default="",
                    help="write the machine-readable SLO report JSON here "
                         "(scenario mode)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    dest="deadline_ms",
                    help="per-request deadline forwarded to the server "
                         "(0 = server default)")
    ap.add_argument("--short", type=int, default=480,
                    help="short side of generated images (long side is "
                         "--long); pick at or under the server's bucket "
                         "scale")
    ap.add_argument("--long", type=int, default=640, dest="long_")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="per-request client wait")
    ap.add_argument("--assert-2xx", action="store_true", dest="assert_2xx",
                    help="exit 1 unless every response was 2xx (stderr "
                         "names the offending statuses)")
    ap.add_argument("--fabric", action="store_true",
                    help="target is a fabric router: diff its /metrics "
                         "per-member request counters around each "
                         "scenario and report member_share (TCP only)")
    ap.add_argument("--capture-check", action="store_true",
                    dest="capture_check",
                    help="diff the server's /metrics flywheel captured "
                         "counter around the run and exit 1 unless it "
                         "matches 2xx submits / capture sample rate "
                         "within --capture-tolerance (catches silent "
                         "capture loss in smoke scripts)")
    ap.add_argument("--capture-tolerance", type=float, default=0.1,
                    dest="capture_tolerance",
                    help="--capture-check: allowed relative deviation "
                         "of captured-delta from the expected count")
    ap.add_argument("--streams", type=int, default=0,
                    help="stream mode: this many concurrent sequenced "
                         "streams against POST /stream (0 = classic "
                         "request mode)")
    ap.add_argument("--fps", type=float, default=10.0,
                    help="stream mode: per-stream frame rate (0 = send "
                         "frames back-to-back)")
    ap.add_argument("--frames", type=int, default=32,
                    help="stream mode: frames per stream")
    ap.add_argument("--motion", action="append", choices=MOTIONS,
                    dest="motions", default=None,
                    help="stream mode: motion profile (repeatable — one "
                         "scenario per profile; default static)")
    ap.add_argument("--cut-every", type=int, default=8, dest="cut_every",
                    help="scene-cut profile: frames between scene "
                         "changes")
    ap.add_argument("--skip-floor", type=float, default=0.0,
                    dest="skip_floor",
                    help="stream mode: attach this skip_fraction floor "
                         "to the static-profile report row (what "
                         "perf_gate.py enforces)")
    ap.add_argument("--p99-ceiling-ms", type=float, default=0.0,
                    dest="p99_ceiling_ms",
                    help="stream mode: per-stream p99 ceiling attached "
                         "to every report row; multi-model mode: the "
                         "isolation p99 ceiling attached to the "
                         "non-burst models in the burst row (what "
                         "perf_gate.py enforces)")
    ap.add_argument("--models", default="",
                    help="multi-model mode: ID=SHARE mix (e.g. "
                         "a=0.7,b=0.3) — every request carries a "
                         "'model' field drawn from this mix against a "
                         "serve.py --models pool")
    ap.add_argument("--burst-model", default="", dest="burst_model",
                    help="multi-model mode: the model whose requests "
                         "all fire back-to-back in the burst scenario "
                         "(default: first in the --models mix)")
    ap.add_argument("--throughput-floor", type=float, default=0.0,
                    dest="throughput_floor",
                    help="multi-model mode: attach this aggregate "
                         "imgs_per_sec floor to the mixed report row "
                         "(what perf_gate.py enforces)")
    ap.add_argument("--cascade", action="store_true",
                    help="cascade mode: the target serves with a "
                         "cascade router (serve.py --cascade) — run the "
                         "big_only baseline and gated cascade scenarios "
                         "over identical payloads and report "
                         "escalation_rate, per-class p99, and detection "
                         "agreement vs the big model")
    ap.add_argument("--speedup-floor", type=float, default=1.0,
                    dest="speedup_floor",
                    help="cascade mode: perf_gate floor on cascade "
                         "imgs_per_sec over big-only imgs_per_sec "
                         "(default 1.0 — the cascade must not lose to "
                         "always-big; 0 = no pin)")
    ap.add_argument("--agreement-floor", type=float, default=0.0,
                    dest="agreement_floor",
                    help="cascade mode: perf_gate floor on mean "
                         "detection agreement between the cascade's "
                         "answers and the big model's on the same "
                         "images (0 = no pin)")
    ap.add_argument("--watch-check", action="store_true",
                    dest="watch_check",
                    help="scrape the target's /alerts after the run and "
                         "assert the alert set matches expectations: "
                         "with no --watch-expect nothing may have fired "
                         "at all (the clean-traffic contract — a "
                         "fire-then-resolve during a steady run is "
                         "still an SLO breach); each --watch-expect "
                         "NAME must have fired (firing now or resolved "
                         "in the history), and nothing outside the "
                         "expected set may still be firing.  Exit 1 "
                         "with the mismatch on stderr; an 'alerts' "
                         "block joins each scenario summary")
    ap.add_argument("--watch-expect", action="append", default=[],
                    dest="watch_expect", metavar="NAME",
                    help="--watch-check: this alertname must have fired "
                         "by the end of the run (repeatable)")
    ap.add_argument("--trace-sample", type=float, default=0.0,
                    dest="trace_sample",
                    help="fraction of requests that carry a client-minted"
                         " distributed-trace id in the 'trace' doc field "
                         "(seeded); the server must echo it back — a "
                         "mismatch fails the run.  Output lines and "
                         "--report rows gain traced / tail_kept counts")
    return ap.parse_args(argv)


def parse_model_mix(spec):
    """``a=0.7,b=0.3`` → ordered ``[(id, normalized_share), ...]``."""
    mix = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        mid, eq, share = part.partition("=")
        if not mid or not eq:
            raise SystemExit(f"loadgen: bad --models entry {part!r} "
                             "(want ID=SHARE)")
        try:
            val = float(share)
        except ValueError:
            raise SystemExit(f"loadgen: bad --models share {share!r}")
        if val <= 0:
            raise SystemExit(f"loadgen: --models share for {mid!r} must "
                             "be positive")
        if any(m == mid for m, _ in mix):
            raise SystemExit(f"loadgen: duplicate model {mid!r}")
        mix.append((mid, val))
    if not mix:
        raise SystemExit("loadgen: --models given but empty")
    total = sum(v for _, v in mix)
    return [(m, v / total) for m, v in mix]


def make_payloads(args, seed=None, size_mix=False):
    rng = np.random.RandomState(args.seed if seed is None else seed)
    docs = []
    for i in range(args.n):
        h, w = ((args.short, args.long_) if i % 2 == 0
                else (args.long_, args.short))
        if size_mix:
            # adversarial mix: anywhere from tiny thumbnails up to the
            # full size, orientation re-flipped at random
            h = int(rng.randint(16, max(h, 17)))
            w = int(rng.randint(16, max(w, 17)))
        else:
            dh, dw = rng.randint(0, max(min(h, w) // 4, 1), 2)
            h, w = max(h - dh, 16), max(w - dw, 16)
        img = rng.randint(0, 255, (h, w, 3), dtype=np.uint8)
        doc = encode_image_payload(img)
        if args.deadline_ms > 0:
            doc["deadline_ms"] = args.deadline_ms
        if (getattr(args, "trace_sample", 0.0) > 0
                and rng.random_sample() < args.trace_sample):
            # client-minted trace id (bare 32-hex = root context); the
            # server echoes it under "trace" in the response
            doc["trace"] = rng.bytes(16).hex()
        docs.append(doc)
    return docs


def schedule(scenario, n, rate, burst=8):
    """Fire offsets (seconds from t0) for ``n`` requests.  All profiles
    hold the same AVERAGE rate so their reports compare; they differ only
    in arrival clumping."""
    if rate <= 0:
        return [0.0] * n
    if scenario == "bursty":
        burst = max(int(burst), 1)
        return [(i // burst) * (burst / rate) for i in range(n)]
    return [i / rate for i in range(n)]  # steady / size-mix


def profile_schedule(profile, n, rate):
    """Fire offsets for a time-varying profile (``PROFILE_SEGMENTS``),
    plus the serialized segment schedule ``[{requests, rate, t0_s}, …]``
    that goes into the report row — the run is reproducible from the doc
    alone.  Unlike :func:`schedule`, profiles deliberately VARY the
    rate: the shape is the test."""
    fracs = PROFILE_SEGMENTS[profile]
    offsets, segments = [], []
    t = 0.0
    remaining = n
    for i, (frac, mult) in enumerate(fracs):
        k = remaining if i == len(fracs) - 1 \
            else min(int(round(n * frac)), remaining)
        seg_rate = rate * mult if rate > 0 else 0.0
        segments.append({"requests": k, "rate": round(seg_rate, 3),
                         "t0_s": round(t, 3)})
        for j in range(k):
            offsets.append(t + (j / seg_rate if seg_rate > 0 else 0.0))
        if k and seg_rate > 0:
            t = offsets[-1] + 1.0 / seg_rate
        remaining -= k
        if remaining <= 0:
            break
    return offsets, segments


class FleetWatcher:
    """Samples a fabric router's ready-member count through ``/readyz``
    while a profile run is in flight — the member-count-vs-time series
    behind ``time_to_scale_s`` (how long the autoscaler took to grow the
    fleet after the load arrived) and the scale-up/drain-back story in
    the autoscale report."""

    def __init__(self, host, port, poll_s=0.3):
        self.host, self.port = host, port
        self.poll_s = max(float(poll_s), 0.05)
        self.samples = []  # (t_rel_s, ready_members)
        self._stop = threading.Event()
        self._thread = None

    def _sample(self):
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=5.0)
        try:
            conn.request("GET", "/readyz")
            doc = json.loads(conn.getresponse().read())
            return int(doc.get("ready_members", 0))
        except (OSError, ValueError):
            return None
        finally:
            conn.close()

    def start(self):
        t0 = time.monotonic()

        def run():
            while not self._stop.is_set():
                v = self._sample()
                if v is not None:
                    self.samples.append(
                        (round(time.monotonic() - t0, 3), v))
                self._stop.wait(self.poll_s)

        self._thread = threading.Thread(target=run, daemon=True,
                                        name="fleet-watcher")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)

    def report(self):
        """``{start, peak, end, time_to_scale_s, samples}`` — ``None``
        time_to_scale_s means the fleet never grew past its starting
        size (a flat run, or the authority held)."""
        s = list(self.samples)
        if not s:
            return {}
        start = s[0][1]
        tts = next((t for t, v in s if v > start), None)
        return {"start": start, "peak": max(v for _, v in s),
                "end": s[-1][1],
                "time_to_scale_s": tts,
                "samples": s}


def fabric_engine_recompiles(host, port, timeout=10.0):
    """``member → engine 'recompiles' counter`` from a fabric router's
    ``/metrics`` engines fold — diffed around a profile run (common
    members only) for the report's zero-recompile-during-scale assert."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("GET", "/metrics")
        doc = json.loads(conn.getresponse().read())
    except (OSError, ValueError):
        return {}
    finally:
        conn.close()
    engines = doc.get("engines", {})
    out = {}
    for name, e in engines.items():
        if isinstance(e, dict):
            out[name] = int((e.get("counters") or {})
                            .get("recompiles", 0) or 0)
    return out


def tcp_request(host, port, doc, timeout):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", "/predict", body=json.dumps(doc).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def fabric_member_requests(host, port, timeout=10.0):
    """``member name → cumulative routed-request count`` from a fabric
    router's ``/metrics``; ``{}`` when the endpoint is unreachable or not
    a fabric router (a mid-chaos snapshot must not kill the run)."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        doc = json.loads(resp.read())
    except (OSError, ValueError):
        return {}
    finally:
        conn.close()
    members = doc.get("fabric", {}).get("members", {})
    return {name: m.get("requests", 0) for name, m in members.items()
            if isinstance(m, dict)}


def fold_flywheel_sections(doc):
    """Fold a ``/metrics`` doc's flywheel stats into one
    ``{"captured", "sample_every"}`` view.  A single engine carries a
    top-level ``flywheel`` section; a fabric router instead folds member
    metrics under ``engines``, so fleet capture sums ``captured`` across
    members (``sample_every`` is the max — the most conservative
    expected-capture divisor).  ``{}`` when nothing captures."""
    fw = doc.get("flywheel")
    if isinstance(fw, dict):
        return {"captured": int(fw.get("captured", 0)),
                "sample_every": max(int(fw.get("sample_every", 1)), 1)}
    captured, sample_every, found = 0, 1, False
    engines = doc.get("engines")
    if isinstance(engines, dict):
        for m in engines.values():
            sub = m.get("flywheel") if isinstance(m, dict) else None
            if isinstance(sub, dict):
                found = True
                captured += int(sub.get("captured", 0))
                sample_every = max(sample_every,
                                   int(sub.get("sample_every", 1)))
    if not found:
        return {}
    return {"captured": captured, "sample_every": sample_every}


def flywheel_capture_stats(args, timeout=10.0):
    """``{"captured": n, "sample_every": k}`` from the target server's
    ``/metrics`` flywheel section (TCP or Unix socket) — folded across
    fabric members when the target is a router; ``{}`` when the
    endpoint is unreachable or capture is not enabled there."""
    try:
        if args.unix_socket:
            status, doc = unix_http_request(args.unix_socket, "GET",
                                            "/metrics", timeout=timeout)
        else:
            conn = http.client.HTTPConnection(args.host, args.port,
                                              timeout=timeout)
            try:
                conn.request("GET", "/metrics")
                resp = conn.getresponse()
                status, doc = resp.status, json.loads(resp.read())
            finally:
                conn.close()
    except (OSError, ValueError):
        return {}
    if status != 200 or not isinstance(doc, dict):
        return {}
    return fold_flywheel_sections(doc)


def trace_stats(args, timeout=10.0):
    """``{"spans_emitted": n, "tail_kept": k}`` from the target's
    ``/metrics`` trace section (engine server or fabric router); ``{}``
    when the endpoint is unreachable or tracing is off there."""
    try:
        if args.unix_socket:
            status, doc = unix_http_request(args.unix_socket, "GET",
                                            "/metrics", timeout=timeout)
        else:
            conn = http.client.HTTPConnection(args.host, args.port,
                                              timeout=timeout)
            try:
                conn.request("GET", "/metrics")
                resp = conn.getresponse()
                status, doc = resp.status, json.loads(resp.read())
            finally:
                conn.close()
    except (OSError, ValueError):
        return {}
    if status != 200 or not isinstance(doc, dict):
        return {}
    tr = doc.get("trace")
    if not isinstance(tr, dict):
        return {}
    return {k: int(tr.get(k, 0))
            for k in ("spans_emitted", "tail_kept")}


def watch_alerts_doc(args, timeout=10.0):
    """The target's ``/alerts`` document (a serve.py --watch process),
    ``{}`` when the route is absent (watchtower off there) or the
    target is unreachable."""
    try:
        if args.unix_socket:
            status, doc = unix_http_request(args.unix_socket, "GET",
                                            "/alerts", timeout=timeout)
        else:
            conn = http.client.HTTPConnection(args.host, args.port,
                                              timeout=timeout)
            try:
                conn.request("GET", "/alerts")
                resp = conn.getresponse()
                status, doc = resp.status, json.loads(resp.read())
            finally:
                conn.close()
    except (OSError, ValueError):
        return {}
    return doc if status == 200 and isinstance(doc, dict) else {}


def watch_alert_names(doc):
    """``(firing_names, fired_names)`` from an ``/alerts`` doc — fired
    covers both currently-firing and already-resolved instances (and
    silenced ones that reached the firing state: a silence hides the
    page, not the fact)."""
    firing = sorted({a.get("alert", "?")
                     for a in (doc.get("firing") or [])})
    fired = sorted({a.get("alert", "?")
                    for a in (doc.get("firing") or [])
                    + (doc.get("resolved") or [])
                    + [a for a in (doc.get("silenced") or [])
                       if a.get("state") == "firing"]})
    return firing, fired


def watch_check_failure(doc, expected):
    """None when the target's alert state matches ``expected`` (the
    --watch-expect alertnames), else the stderr failure line.  No
    expectations ⇒ the clean-traffic contract: nothing may have fired
    at all.  With expectations: every named alert must have fired, and
    nothing OUTSIDE the expected set may still be firing (a leftover
    firing alert means the injected fault never cleared).  A target
    with no /alerts route fails loudly — pointing --watch-check at a
    watch-off server is itself a smoke-script bug."""
    if not doc:
        return ("loadgen: --watch-check failed: target exposes no "
                "/alerts route (serve.py --watch not active?)")
    firing, fired = watch_alert_names(doc)
    if not expected:
        if fired:
            return (f"loadgen: --watch-check failed: expected a clean "
                    f"pass but {fired} fired (still firing: "
                    f"{firing or '[]'})")
        return None
    missing = sorted(set(expected) - set(fired))
    if missing:
        return (f"loadgen: --watch-check failed: expected {missing} to "
                f"fire; fired: {fired or '[]'}")
    stray = sorted(set(firing) - set(expected))
    if stray:
        return (f"loadgen: --watch-check failed: {stray} still firing "
                f"beyond the expected set {sorted(set(expected))}")
    return None


def trace_echo_failure(results):
    """None when every echoed trace id matched what was sent, else the
    stderr failure line (run_requests records mismatches as errors on
    otherwise-2xx results)."""
    mism = sorted({r[3] for r in results
                   if r[3] and r[3].startswith("trace echo mismatch")})
    if not mism:
        return None
    return (f"loadgen: trace echo assertion failed "
            f"({len(mism)} distinct): {'; '.join(mism[:3])}")


def capture_check_failure(before, after, ok_submits, tolerance):
    """None when the server's captured-count delta matches
    ``ok_submits / sample_every`` within ``tolerance`` (relative, with
    ±1 absolute slack for stride phase), else the stderr failure line.
    Missing flywheel sections fail loudly — a smoke script passing
    ``--capture-check`` against a capture-off server is itself a bug."""
    if not after:
        return ("loadgen: --capture-check failed: target exposes no "
                "flywheel section on /metrics (capture not enabled?)")
    sample_every = after["sample_every"]
    delta = after["captured"] - (before.get("captured", 0) if before else 0)
    expected = ok_submits / sample_every
    slack = max(1.0, tolerance * expected)
    if abs(delta - expected) > slack:
        return (f"loadgen: --capture-check failed: captured delta {delta} "
                f"vs expected {expected:.1f} ({ok_submits} 2xx submits / "
                f"sample_every {sample_every}, tolerance ±{slack:.1f})")
    return None


def member_share(before: dict, after: dict) -> dict:
    """Per-member fraction of the requests routed between two snapshots
    (members that joined mid-window count from zero)."""
    deltas = {name: after[name] - before.get(name, 0) for name in after}
    total = sum(d for d in deltas.values() if d > 0)
    return {name: round(max(d, 0) / max(total, 1), 4)
            for name, d in sorted(deltas.items())}


def run_requests(args, docs, offsets):
    """Fire every payload at its offset (open loop); returns
    ``(results, wall_s)`` where results[i] is
    ``(status, latency_s, queue_wait_ms, error_str, t_done_s)`` —
    ``t_done_s`` is the completion instant relative to the run start,
    what the time-to-recover failover metric is computed from."""
    n = len(docs)
    results = [None] * n

    def fire(i):
        t0 = time.perf_counter()
        try:
            if args.unix_socket:
                status, resp = unix_http_request(
                    args.unix_socket, "POST", "/predict", docs[i],
                    timeout=args.timeout)
            else:
                status, resp = tcp_request(args.host, args.port, docs[i],
                                           args.timeout)
        except Exception as e:  # noqa: BLE001 — a dead server is a result
            results[i] = (0, time.perf_counter() - t0, None,
                          f"{type(e).__name__}: {e}",
                          time.perf_counter() - t_start)
            return
        err = None
        sent = docs[i].get("trace")
        if sent and 200 <= status < 300 and resp.get("trace") != sent:
            err = (f"trace echo mismatch: sent {sent}, got "
                   f"{resp.get('trace')!r}")
        results[i] = (status, time.perf_counter() - t0,
                      resp.get("queue_wait_ms"), err,
                      time.perf_counter() - t_start)

    t_start = time.perf_counter()
    threads = []
    for i in range(n):
        lag = t_start + offsets[i] - time.perf_counter()
        if lag > 0:  # open loop: fire on the clock, never on replies
            time.sleep(lag)
        th = threading.Thread(target=fire, args=(i,))
        th.start()
        threads.append(th)
    for th in threads:
        th.join()
    return results, time.perf_counter() - t_start


def summarize(results, wall):
    n = len(results)
    status_counts = {}
    for r in results:
        status_counts[str(r[0])] = status_counts.get(str(r[0]), 0) + 1
    ok = [r for r in results if 200 <= r[0] < 300]
    lat_ms = np.asarray([r[1] for r in ok]) * 1e3
    qw = [r[2] for r in ok if r[2] is not None]
    # availability: 2xx over NON-SHED submits — 503s are deliberate
    # backpressure/degradation (the shed contract), not unavailability;
    # 5xx and transport errors (status 0) are
    non_shed = n - status_counts.get("503", 0)
    # time-to-recover: first hard failure (5xx/transport, NOT the shed
    # 503s — same exclusion as availability) → the next 2xx COMPLETION
    # after it; null when the run never hard-failed (or never
    # recovered) — the failover metric replica chaos runs gate on
    fail_ts = sorted(r[4] for r in results
                     if r[0] == 0 or (r[0] >= 500 and r[0] != 503))
    recover_s = None
    if fail_ts:
        after = [r[4] for r in ok if r[4] > fail_ts[0]]
        recover_s = round(min(after) - fail_ts[0], 3) if after else None
    out = {
        "requests": n,
        "status": dict(sorted(status_counts.items())),
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3) if ok else None,
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3) if ok else None,
        "error_rate": round((n - len(ok)) / max(n, 1), 4),
        "availability": round(len(ok) / max(non_shed, 1), 4),
        "time_to_recover_s": recover_s,
        "mean_queue_wait_ms": (round(float(np.mean(qw)), 3) if qw else None),
        "imgs_per_sec": round(len(ok) / wall, 3) if wall > 0 else None,
        "wall_s": round(wall, 3),
    }
    errors = sorted({r[3] for r in results if r[3]})
    if errors:
        out["errors"] = errors[:5]
    return out


def assert_2xx_failure(results):
    """None when every response was 2xx, else the stderr line naming each
    offending status code and its count (0 = transport error)."""
    bad = {}
    for r in results:
        if not 200 <= r[0] < 300:
            bad[r[0]] = bad.get(r[0], 0) + 1
    if not bad:
        return None
    total = sum(bad.values())
    parts = ", ".join(
        f"{ct}x status {st}" if st else f"{ct}x transport error"
        for st, ct in sorted(bad.items()))
    errors = sorted({r[3] for r in results if r[3]})
    msg = (f"loadgen: --assert-2xx failed: {total}/{len(results)} "
           f"responses were not 2xx ({parts})")
    if errors:
        msg += f"; first errors: {'; '.join(errors[:3])}"
    return msg


# -- stream mode (ISSUE 14) ----------------------------------------------


class StreamConn:
    """One persistent keep-alive HTTP connection (TCP or Unix socket) —
    the per-stream transport.  A camera holds its connection open; a
    transport failure reconnects once, then reports status 0."""

    def __init__(self, args):
        self.args = args
        self.conn = None

    def _connect(self):
        a = self.args
        if a.unix_socket:
            sock_path, timeout = a.unix_socket, a.timeout

            class Conn(http.client.HTTPConnection):
                def __init__(self):
                    super().__init__("localhost", timeout=timeout)

                def connect(self):
                    import socket as _socket
                    self.sock = _socket.socket(_socket.AF_UNIX,
                                               _socket.SOCK_STREAM)
                    self.sock.settimeout(timeout)
                    self.sock.connect(sock_path)

            self.conn = Conn()
        else:
            self.conn = http.client.HTTPConnection(a.host, a.port,
                                                   timeout=a.timeout)

    def post_frame(self, doc):
        """One frame → (per-frame status, response doc).  The HTTP
        envelope is 200 whenever the body parsed; the status that matters
        is the per-line one inside the NDJSON reply."""
        body = (json.dumps(doc) + "\n").encode()
        for attempt in (0, 1):
            try:
                if self.conn is None:
                    self._connect()
                self.conn.request(
                    "POST", "/stream", body=body,
                    headers={"Content-Type": "application/x-ndjson"})
                resp = self.conn.getresponse()
                raw = resp.read()
                if resp.status != 200:
                    return resp.status, {}
                line = raw.decode().strip().splitlines()
                out = json.loads(line[-1]) if line else {}
                return int(out.get("status", 0)), out
            except (OSError, ValueError) as e:
                self.close()
                if attempt:
                    return 0, {"error": f"{type(e).__name__}: {e}"}
        return 0, {}

    def close(self):
        if self.conn is not None:
            try:
                self.conn.close()
            finally:
                self.conn = None


def make_stream_frames(rng, motion, n, h, w, cut_every=8):
    """``n`` consecutive (h, w, 3) uint8 frames of one motion profile."""
    scene = rng.randint(0, 255, (h, w, 3), dtype=np.uint8)
    frames = []
    for i in range(n):
        if motion == "pan":
            # the whole scene translates: every pixel changes, mean
            # absolute delta is large — the gate must take the full path
            frames.append(np.roll(scene, 3 * (i + 1), axis=1))
        elif motion == "scene-cut":
            if i and i % max(cut_every, 1) == 0:
                scene = rng.randint(0, 255, (h, w, 3), dtype=np.uint8)
            frames.append(scene.copy())
        else:  # static: ±1 sensor noise on ~5% of pixels
            f = scene.copy()
            k = max((h * w) // 20, 1)
            ys = rng.randint(0, h, k)
            xs = rng.randint(0, w, k)
            f[ys, xs] = np.clip(
                f[ys, xs].astype(np.int16)
                + rng.choice((-1, 1), (k, 1)), 0, 255).astype(np.uint8)
            frames.append(f)
    return frames


def server_metrics_doc(args, timeout=10.0):
    """The target's full ``/metrics`` doc (``{}`` when unreachable)."""
    try:
        if args.unix_socket:
            status, doc = unix_http_request(args.unix_socket, "GET",
                                            "/metrics", timeout=timeout)
        else:
            conn = http.client.HTTPConnection(args.host, args.port,
                                              timeout=timeout)
            try:
                conn.request("GET", "/metrics")
                resp = conn.getresponse()
                status, doc = resp.status, json.loads(resp.read())
            finally:
                conn.close()
    except (OSError, ValueError):
        return {}
    if status != 200 or not isinstance(doc, dict):
        return {}
    return doc


def server_counters(args, timeout=10.0):
    """The target's ``/metrics`` engine counters (``{}`` when
    unreachable) — diffed around a scenario for ``dispatches_per_frame``."""
    return server_metrics_doc(args, timeout=timeout).get("counters") or {}


def run_stream_scenario(args, motion, idx):
    """One motion profile: ``--streams`` concurrent closed-loop senders.
    Returns ``(per_stream_results, per_stream_dropped, wall_s)`` where
    results[s] is a list of ``(status, latency_s, skipped)``."""
    per_results = [[] for _ in range(args.streams)]
    per_dropped = [0] * args.streams
    interval = 1.0 / args.fps if args.fps > 0 else 0.0

    def run_one(si):
        rng = np.random.RandomState(args.seed + 1000 * idx + si)
        h, w = ((args.short, args.long_) if si % 2 == 0
                else (args.long_, args.short))
        frames = make_stream_frames(rng, motion, args.frames, h, w,
                                    cut_every=args.cut_every)
        conn = StreamConn(args)
        seq = 0
        t0 = time.perf_counter()
        for i, frame in enumerate(frames):
            target = t0 + i * interval
            now = time.perf_counter()
            if interval and now > target + interval:
                # more than a full slot late: a camera drops the frame
                # rather than queueing a stale one
                per_dropped[si] += 1
                continue
            if now < target:
                time.sleep(target - now)
            seq += 1
            doc = {"stream_id": f"{motion}-{si}", "seq": seq,
                   **encode_image_payload(frame)}
            if args.deadline_ms > 0:
                doc["deadline_ms"] = args.deadline_ms
            ts = time.perf_counter()
            status, resp = conn.post_frame(doc)
            per_results[si].append((status, time.perf_counter() - ts,
                                    bool(resp.get("skipped"))))
        conn.close()

    t_start = time.perf_counter()
    threads = [threading.Thread(target=run_one, args=(s,))
               for s in range(args.streams)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return per_results, per_dropped, time.perf_counter() - t_start


def summarize_streams(args, motion, per_results, per_dropped, wall):
    """One scenario's ``mxr_stream_report`` row.  ``p99_ms`` is the MAX
    over per-stream p99s — the SLO a fleet operator actually owes each
    camera — with the full per-stream list alongside."""
    flat = [r for rs in per_results for r in rs]
    status_counts = {}
    for r in flat:
        status_counts[str(r[0])] = status_counts.get(str(r[0]), 0) + 1
    ok = [r for r in flat if 200 <= r[0] < 300]
    per_stream_p99 = []
    for rs in per_results:
        lat = [r[1] for r in rs if 200 <= r[0] < 300]
        per_stream_p99.append(
            round(float(np.percentile(np.asarray(lat) * 1e3, 99)), 3)
            if lat else None)
    p99s = [p for p in per_stream_p99 if p is not None]
    all_lat = np.asarray([r[1] for r in ok]) * 1e3
    skipped = sum(1 for r in ok if r[2])
    return {
        "name": motion,
        "streams": args.streams,
        "fps": args.fps,
        "frames_per_stream": args.frames,
        "frames_sent": len(flat),
        "frames_dropped": sum(per_dropped),
        "status": dict(sorted(status_counts.items())),
        "p50_ms": (round(float(np.percentile(all_lat, 50)), 3)
                   if ok else None),
        "p99_ms": max(p99s) if p99s else None,
        "per_stream_p99_ms": per_stream_p99,
        "error_rate": round((len(flat) - len(ok)) / max(len(flat), 1), 4),
        "skip_fraction": round(skipped / max(len(ok), 1), 4),
        "imgs_per_sec": round(len(ok) / wall, 3) if wall > 0 else None,
        "wall_s": round(wall, 3),
    }


def stream_main(args):
    """Stream-mode driver: one scenario per ``--motion`` profile, one
    ``mxr_stream_report`` doc for the gate."""
    motions = args.motions or ["static"]
    rows = []
    all_status = []
    for idx, motion in enumerate(motions):
        before = server_counters(args, timeout=args.timeout)
        per_results, per_dropped, wall = run_stream_scenario(
            args, motion, idx)
        after = server_counters(args, timeout=args.timeout)
        row = summarize_streams(args, motion, per_results, per_dropped,
                                wall)
        if after and row["frames_sent"]:
            row["dispatches_per_frame"] = round(
                (after.get("dispatches", 0) - before.get("dispatches", 0))
                / row["frames_sent"], 4)
        if motion == "static" and args.skip_floor > 0:
            row["skip_fraction_floor"] = args.skip_floor
        if args.p99_ceiling_ms > 0:
            row["p99_ceiling_ms"] = args.p99_ceiling_ms
        rows.append(row)
        all_status.extend(r[0] for rs in per_results for r in rs)
        print(json.dumps({"scenario": motion, **row}))

    if args.report:
        doc = {"schema": STREAM_REPORT_SCHEMA, "version": REPORT_VERSION,
               "scenarios": rows}
        with open(args.report, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)

    if args.assert_2xx:
        bad = [s for s in all_status if not 200 <= s < 300]
        if bad:
            counts = {}
            for s in bad:
                counts[s] = counts.get(s, 0) + 1
            parts = ", ".join(
                f"{ct}x status {st}" if st else f"{ct}x transport error"
                for st, ct in sorted(counts.items()))
            print(f"loadgen: --assert-2xx failed: {len(bad)}/"
                  f"{len(all_status)} frames were not 2xx ({parts})",
                  file=sys.stderr)
            sys.exit(1)


# -- multi-model mode (ISSUE 15) ------------------------------------------


MM_MODEL_KEYS = ("requests", "status", "p50_ms", "p99_ms", "error_rate",
                 "availability", "mean_queue_wait_ms")


def assign_models(mix, n, rng):
    """Model id per request slot: a seeded weighted draw, then a
    guarantee that every model in the mix appears at least once (a tiny
    ``--n`` must still exercise every tenant)."""
    ids = [m for m, _ in mix]
    shares = np.asarray([s for _, s in mix])
    picks = [ids[i] for i in rng.choice(len(ids), size=n, p=shares)]
    for j, mid in enumerate(ids):
        if n > j and mid not in picks:
            picks[j] = mid
    return picks


def multimodel_offsets(scenario, picks, burst_model, n, rate):
    """Fire offsets for the multi-model profiles.  ``mixed`` is plain
    steady.  ``burst``: non-burst models keep their steady slots while
    every burst-model request fires at one instant a quarter into the
    window — the sibling models' latency THROUGH that spike is the
    isolation measurement."""
    steady = schedule("steady", n, rate)
    if scenario != "burst" or rate <= 0:
        return steady
    burst_at = steady[-1] * 0.25
    return [burst_at if picks[i] == burst_model else steady[i]
            for i in range(n)]


def summarize_per_model(picks, results, wall):
    """``model id → per-model summary block`` (the fields the
    MULTIMODEL gate scores), in mix order of first appearance."""
    out = {}
    for mid in dict.fromkeys(picks):
        sub = [r for p, r in zip(picks, results) if p == mid]
        summ = summarize(sub, wall)
        out[mid] = {k: summ[k] for k in MM_MODEL_KEYS if k in summ}
    return out


def multimodel_main(args):
    """Multi-model driver: the ``mixed`` (aggregate throughput) and
    ``burst`` (tenant isolation) scenarios against one model pool; one
    ``mxr_multimodel_report`` doc for the gate."""
    mix = parse_model_mix(args.models)
    burst_model = args.burst_model or mix[0][0]
    if burst_model not in (m for m, _ in mix):
        raise SystemExit(f"loadgen: --burst-model {burst_model!r} not "
                         "in the --models mix")
    rows = []
    all_results = []
    for idx, scenario in enumerate(("mixed", "burst")):
        docs = make_payloads(args, seed=args.seed + idx)
        rng = np.random.RandomState(args.seed + 7000 + idx)
        picks = assign_models(mix, args.n, rng)
        for doc, mid in zip(docs, picks):
            doc["model"] = mid
        offsets = multimodel_offsets(scenario, picks, burst_model,
                                     args.n, args.rate)
        results, wall = run_requests(args, docs, offsets)
        all_results.extend(results)
        out = summarize(results, wall)
        out["models"] = summarize_per_model(picks, results, wall)
        row = {"name": scenario,
               "mix": {m: round(s, 4) for m, s in mix},
               **{k: v for k, v in out.items()
                  if k in ("requests", "status", "p50_ms", "p99_ms",
                           "error_rate", "availability", "imgs_per_sec",
                           "wall_s", "models")}}
        if scenario == "burst":
            row["burst_model"] = burst_model
            if args.p99_ceiling_ms > 0:
                row["isolation_p99_ceiling_ms"] = args.p99_ceiling_ms
        elif args.throughput_floor > 0:
            row["imgs_per_sec_floor"] = args.throughput_floor
        rows.append(row)
        print(json.dumps({"scenario": scenario, **out}))

    if args.report:
        doc = {"schema": MULTIMODEL_REPORT_SCHEMA,
               "version": REPORT_VERSION, "scenarios": rows}
        with open(args.report, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)

    if args.assert_2xx:
        msg = assert_2xx_failure(all_results)
        if msg is not None:
            print(msg, file=sys.stderr)
            sys.exit(1)


# -- cascade mode (ISSUE 19) ----------------------------------------------


def run_cascade_requests(args, docs, offsets):
    """:func:`run_requests` with the response doc RETAINED per result —
    results[i] is ``(status, latency_s, queue_wait_ms, error_str,
    t_done_s, response_doc)``.  Cascade mode needs the bodies: the
    ``cascade`` provenance field (escalated flag → per-class split) and
    the ``detections`` (→ agreement vs the big-only pass)."""
    n = len(docs)
    results = [None] * n

    def fire(i):
        t0 = time.perf_counter()
        try:
            if args.unix_socket:
                status, resp = unix_http_request(
                    args.unix_socket, "POST", "/predict", docs[i],
                    timeout=args.timeout)
            else:
                status, resp = tcp_request(args.host, args.port, docs[i],
                                           args.timeout)
        except Exception as e:  # noqa: BLE001 — a dead server is a result
            results[i] = (0, time.perf_counter() - t0, None,
                          f"{type(e).__name__}: {e}",
                          time.perf_counter() - t_start, {})
            return
        results[i] = (status, time.perf_counter() - t0,
                      resp.get("queue_wait_ms"), None,
                      time.perf_counter() - t_start, resp)

    t_start = time.perf_counter()
    threads = []
    for i in range(n):
        lag = t_start + offsets[i] - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        th = threading.Thread(target=fire, args=(i,))
        th.start()
        threads.append(th)
    for th in threads:
        th.join()
    return results, time.perf_counter() - t_start


def latency_class_block(results):
    """p50/p99 over one escalation class of 6-tuple results (the
    per-class split the CASCADE gate trends)."""
    lat = np.asarray([r[1] for r in results
                      if 200 <= r[0] < 300]) * 1e3
    return {
        "requests": len(results),
        "p50_ms": (round(float(np.percentile(lat, 50)), 3)
                   if lat.size else None),
        "p99_ms": (round(float(np.percentile(lat, 99)), 3)
                   if lat.size else None),
    }


def cascade_agreement(cascade_results, big_results):
    """Mean :func:`detection_agreement` between the cascade's answers
    and the big model's over the SAME images (index-matched — both
    passes are built from the same seed), None when no pair completed.
    The big-only detections are the reference ("labels") side."""
    from mx_rcnn_tpu.flywheel.fleet import detection_agreement
    vals = []
    for c, b in zip(cascade_results, big_results):
        if not (200 <= c[0] < 300 and 200 <= b[0] < 300):
            continue
        vals.append(detection_agreement(c[5].get("detections") or [],
                                        b[5].get("detections") or []))
    return round(float(np.mean(vals)), 4) if vals else None


def cascade_main(args):
    """Cascade-mode driver: the ``big_only`` baseline then the gated
    ``cascade`` scenario over identical payloads; one
    ``mxr_cascade_report`` doc for the gate."""
    info = server_metrics_doc(args, timeout=args.timeout).get("cascade")
    if not isinstance(info, dict) or not info.get("big"):
        raise SystemExit("loadgen: --cascade target exposes no cascade "
                         "section on /metrics (serve.py --cascade not "
                         "active?)")
    small, big = info.get("small"), info["big"]
    offsets = schedule("steady", args.n, args.rate)
    keep = ("requests", "status", "p50_ms", "p99_ms", "error_rate",
            "availability", "imgs_per_sec", "wall_s")
    rows, all_results = [], []

    # baseline: the same images addressed straight at the big model —
    # what the cascade's throughput and answers are scored against
    docs = make_payloads(args, seed=args.seed)
    for doc in docs:
        doc["model"] = big
    big_results, big_wall = run_cascade_requests(args, docs, offsets)
    all_results.extend(r[:5] for r in big_results)
    big_out = summarize([r[:5] for r in big_results], big_wall)
    rows.append({"name": "big_only", "model": big,
                 **{k: v for k, v in big_out.items() if k in keep}})
    print(json.dumps({"scenario": "big_only", **big_out}))

    # the gated pass: identical payloads (same seed), default routing
    docs = make_payloads(args, seed=args.seed)
    before = dict(info.get("counters") or {})
    results, wall = run_cascade_requests(args, docs, offsets)
    after = server_metrics_doc(args, timeout=args.timeout).get("cascade")
    all_results.extend(r[:5] for r in results)
    out = summarize([r[:5] for r in results], wall)

    ok = [r for r in results if 200 <= r[0] < 300]
    esc = [r for r in ok if (r[5].get("cascade") or {}).get("escalated")]
    small_ans = [r for r in ok
                 if not (r[5].get("cascade") or {}).get("escalated")]
    out["escalation_rate"] = round(len(esc) / max(len(ok), 1), 4)
    out["classes"] = {"answered_small": latency_class_block(small_ans),
                      "escalated": latency_class_block(esc)}
    if isinstance(after, dict):
        # the server's own view of THIS run (counter delta), the
        # cross-check script/cascade_smoke.sh asserts against
        ac, bc = after.get("counters") or {}, before
        dec = ((ac.get("answered_small", 0) - bc.get("answered_small", 0))
               + (ac.get("escalated", 0) - bc.get("escalated", 0)))
        if dec > 0:
            out["server_escalation_rate"] = round(
                (ac.get("escalated", 0) - bc.get("escalated", 0)) / dec, 4)
    agree = cascade_agreement(results, big_results)
    out["agreement"] = agree
    big_ips = big_out.get("imgs_per_sec")
    if big_ips and out.get("imgs_per_sec"):
        out["big_only_imgs_per_sec"] = big_ips
        out["speedup_vs_big"] = round(out["imgs_per_sec"] / big_ips, 4)
    row = {"name": "cascade", "small": small, "big": big,
           "thresh": info.get("thresh"),
           **{k: v for k, v in out.items()
              if k in keep + ("escalation_rate", "server_escalation_rate",
                              "classes", "agreement",
                              "big_only_imgs_per_sec", "speedup_vs_big")}}
    if args.speedup_floor > 0:
        row["speedup_floor"] = args.speedup_floor
    if args.agreement_floor > 0:
        row["agreement_floor"] = args.agreement_floor
    if args.throughput_floor > 0:
        row["imgs_per_sec_floor"] = args.throughput_floor
    rows.append(row)
    print(json.dumps({"scenario": "cascade", **out}))

    if args.report:
        doc = {"schema": CASCADE_REPORT_SCHEMA, "version": REPORT_VERSION,
               "scenarios": rows}
        with open(args.report, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)

    if args.assert_2xx:
        msg = assert_2xx_failure(all_results)
        if msg is not None:
            print(msg, file=sys.stderr)
            sys.exit(1)


def main(argv=None):
    args = parse_args(argv)
    if bool(args.unix_socket) == bool(args.port):
        raise SystemExit("pass exactly one of --port / --unix-socket")
    if args.fabric and not args.port:
        raise SystemExit("--fabric needs a TCP router (--port)")
    if args.cascade:
        if args.models or args.streams > 0:
            raise SystemExit("--cascade is exclusive with --models / "
                             "--streams (the pair comes from the "
                             "server's /metrics)")
        return cascade_main(args)
    if args.models:
        if args.streams > 0:
            raise SystemExit("--models and --streams are exclusive")
        return multimodel_main(args)
    if args.streams > 0:
        return stream_main(args)

    scenarios = args.scenarios or [None]
    report_rows = []
    all_results = []
    capture_before = (flywheel_capture_stats(args, timeout=args.timeout)
                      if args.capture_check else None)
    for idx, scenario in enumerate(scenarios):
        docs = make_payloads(args, seed=args.seed + idx,
                             size_mix=(scenario == "size-mix"))
        segments = None
        if args.profile:
            offsets, segments = profile_schedule(args.profile, args.n,
                                                 args.rate)
        else:
            offsets = schedule(scenario or "steady", args.n, args.rate,
                               burst=args.burst)
        before = (fabric_member_requests(args.host, args.port,
                                         timeout=args.timeout)
                  if args.fabric else None)
        recompiles_before = (fabric_engine_recompiles(
            args.host, args.port, timeout=args.timeout)
            if args.fabric and args.profile else None)
        watcher = None
        if args.fabric and args.profile:
            watcher = FleetWatcher(args.host, args.port,
                                   poll_s=args.fleet_poll_s).start()
        results, wall = run_requests(args, docs, offsets)
        if watcher is not None:
            watcher.stop()
        all_results.extend(results)
        out = summarize(results, wall)
        if args.fabric:
            after = fabric_member_requests(args.host, args.port,
                                           timeout=args.timeout)
            out["member_share"] = member_share(before, after)
            out["fabric_members"] = len(after)
        if args.profile:
            out["profile"] = args.profile
            out["schedule"] = segments
            if watcher is not None:
                fleet = watcher.report()
                out["fleet"] = fleet
                out["time_to_scale_s"] = fleet.get("time_to_scale_s")
            if recompiles_before is not None:
                recompiles_after = fabric_engine_recompiles(
                    args.host, args.port, timeout=args.timeout)
                out["recompiles_during_run"] = sum(
                    recompiles_after[k] - recompiles_before[k]
                    for k in recompiles_after
                    if k in recompiles_before)
            # perf-gate pins for autoscale_report_rows()
            if args.p99_ceiling_ms > 0:
                out["p99_ceiling_ms"] = args.p99_ceiling_ms
            if args.scale_floor > 0:
                out["scale_floor"] = args.scale_floor
            if args.time_to_scale_ceiling_s > 0:
                out["time_to_scale_ceiling_s"] = \
                    args.time_to_scale_ceiling_s
            out["recompile_ceiling"] = 0.0
        if args.trace_sample > 0:
            out["traced"] = sum(1 for d in docs if "trace" in d)
            out["tail_kept"] = trace_stats(
                args, timeout=args.timeout).get("tail_kept")
        if args.watch_check:
            wdoc = watch_alerts_doc(args, timeout=args.timeout)
            firing, fired = watch_alert_names(wdoc)
            out["alerts"] = ({"firing": firing, "fired": fired,
                              "ticks": wdoc.get("ticks")}
                             if wdoc else None)
        if scenario is not None:
            out = {"scenario": scenario, **out}
        if scenario is not None or args.report:
            report_rows.append({"name": scenario or "default", **{
                k: v for k, v in out.items()
                if k in ("requests", "status", "p50_ms", "p99_ms",
                         "error_rate", "availability", "time_to_recover_s",
                         "imgs_per_sec", "wall_s", "member_share",
                         "fabric_members", "traced", "tail_kept",
                         "profile", "schedule", "fleet", "time_to_scale_s",
                         "recompiles_during_run", "p99_ceiling_ms",
                         "scale_floor", "time_to_scale_ceiling_s",
                         "recompile_ceiling", "alerts")}})
        print(json.dumps(out))

    if args.report:
        schema = AUTOSCALE_REPORT_SCHEMA if args.profile else REPORT_SCHEMA
        doc = {"schema": schema, "version": REPORT_VERSION,
               "scenarios": report_rows}
        with open(args.report, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)

    if args.capture_check:
        after = flywheel_capture_stats(args, timeout=args.timeout)
        ok = sum(1 for r in all_results if 200 <= r[0] < 300)
        msg = capture_check_failure(capture_before, after, ok,
                                    args.capture_tolerance)
        if msg is not None:
            print(msg, file=sys.stderr)
            sys.exit(1)

    if args.trace_sample > 0:
        msg = trace_echo_failure(all_results)
        if msg is not None:
            print(msg, file=sys.stderr)
            sys.exit(1)

    if args.watch_check:
        msg = watch_check_failure(
            watch_alerts_doc(args, timeout=args.timeout),
            args.watch_expect)
        if msg is not None:
            print(msg, file=sys.stderr)
            sys.exit(1)

    if args.assert_2xx:
        msg = assert_2xx_failure(all_results)
        if msg is not None:
            print(msg, file=sys.stderr)
            sys.exit(1)


if __name__ == "__main__":
    main()
