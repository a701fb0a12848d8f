"""Load generator and its arithmetic — the serving yardstick.

Runs as a child process (``python benchmark/loadgen.py``, the spec as one
JSON line on standard input) that imports neither jax nor the program, so it
shares no interpreter lock with the server.  It speaks HTTP over the
server's Unix socket with the standard library alone.

Scheduler and payloads are copied from ``scripts/loadgen.py`` (open loop
fired on the clock, never on replies; seeded uint8 bodies in both
orientations).  The arithmetic is redone for a yardstick:

* latency is response end minus the **due** instant (not the firing
  thread's own start), so a stall is charged to every request it delayed;
* percentiles are over **all** requests due in the window: one that
  failed, was shed, came back malformed or never answered counts as slower
  than every answered one (it reads ``miss_ms`` = window + drain);
* ``serve_imgs_per_s`` is good responses whose due time lies in the window
  over the window's length — the drain is not in the denominator;
* how late the generator itself fired (fire - due) is reported.

Every seed offers the same work: the same multiset of image sizes and of
arrival gaps (the exponential distribution's quantiles, scaled to fill the
window exactly), in an order the seed permutes.  So the number of requests
in a window never depends on the seed.
"""

from __future__ import annotations

import base64
import http.client
import json
import math
import socket
import sys
import threading
import time

import numpy as np


# ----------------------------------------------------------------- transport

class _UnixConn(http.client.HTTPConnection):
    def __init__(self, path: str, timeout: float):
        super().__init__("localhost", timeout=timeout)
        self._path = path

    def connect(self):
        self.sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self.sock.settimeout(self.timeout)
        self.sock.connect(self._path)


def http_unix(path: str, method: str, url: str, body: bytes | None = None,
              timeout: float = 60.0):
    """-> (status, parsed JSON or None).  Raises OSError on transport loss."""
    conn = _UnixConn(path, timeout)
    try:
        hdrs = {"Content-Type": "application/json"} if body else {}
        conn.request(method, url, body=body, headers=hdrs)
        resp = conn.getresponse()
        raw = resp.read()
        try:
            return resp.status, json.loads(raw)
        except ValueError:
            return resp.status, None
    finally:
        conn.close()


def wait_ready(path: str, limit_s: float) -> None:
    t_end = time.monotonic() + limit_s
    while time.monotonic() < t_end:
        try:
            if http_unix(path, "GET", "/readyz", timeout=5)[0] == 200:
                return
        except OSError:
            pass
        time.sleep(0.25)
    raise RuntimeError("server never became ready")


# ------------------------------------------------------------------ payloads

def body_sizes(mix: dict) -> list:
    """The pool's (h, w) list — the same for every seed.  Short and long
    sides walk their ranges on strides coprime to the pool size; every
    ``portrait_every``-th body is portrait."""
    n = mix["pool"]
    s0, s1 = mix["short"]
    l0, l1 = mix["long"]
    out = []
    for i in range(n):
        short = s0 + (i * 37) % (s1 - s0 + 1)
        long_ = l0 + (i * 53) % (l1 - l0 + 1)
        portrait = mix["portrait_every"] > 0 and \
            i % mix["portrait_every"] == mix["portrait_every"] - 1
        out.append((long_, short) if portrait else (short, long_))
    return out


def encode_image_payload(img: np.ndarray) -> dict:
    """The request contract of ``POST /predict`` (base64 raw RGB bytes)."""
    img = np.ascontiguousarray(img, np.uint8)
    return {"shape": list(img.shape),
            "data": base64.b64encode(img.tobytes()).decode("ascii")}


def make_bodies(mix: dict, seed: int) -> list:
    """Encoded request bodies (bytes), pixels from the seed."""
    rng = np.random.default_rng([int(seed), 1])
    return [json.dumps(encode_image_payload(
        rng.integers(0, 256, (h, w, 3), dtype=np.uint8))).encode()
        for h, w in body_sizes(mix)]


# ------------------------------------------------------------------ schedule

def arrival_offsets(traffic: dict, seconds: float, seed: int) -> list:
    """Due instants (seconds from the window's start) of an open loop."""
    arrival = traffic["arrival"]
    rate = float(traffic["rate"])
    n = max(int(round(rate * seconds)), 1)
    if arrival == "uniform":
        return [(i + 0.5) / rate for i in range(n)]
    if arrival == "burst":
        b = max(int(traffic.get("burst", 8)), 1)
        return [(i // b) * (b / rate) for i in range(n)]
    if arrival == "poisson":
        # the exponential's quantiles: the same multiset of gaps for every
        # seed, scaled so that they fill the window, in a seeded order
        gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)
        gaps *= seconds / gaps.sum()
        rng = np.random.default_rng([int(seed), 2])
        rng.shuffle(gaps)
        t = np.cumsum(gaps) - gaps[0] * 0.5
        return [float(min(v, seconds - 1e-6)) for v in t]
    raise ValueError(f"unknown arrival {arrival!r}")


def body_order(n: int, pool: int, seed: int) -> list:
    """Which body each request carries: whole turns through the pool, each
    turn in a seeded order, so every body is used equally often."""
    rng = np.random.default_rng([int(seed), 3])
    out = []
    while len(out) < n:
        out.extend(int(i) for i in rng.permutation(pool))
    return out[:n]


# ---------------------------------------------------------------- well-formed

def well_formed(doc, num_classes: int) -> bool:
    if not isinstance(doc, dict):
        return False
    recs = doc.get("detections")
    return isinstance(recs, list) and all(
        isinstance(r, dict)
        and isinstance(r.get("cls"), int) and 0 < r["cls"] < num_classes
        and isinstance(r.get("score"), float) and 0.0 <= r["score"] <= 1.0
        and isinstance(r.get("bbox"), list) and len(r["bbox"]) == 4
        and all(isinstance(c, float) and math.isfinite(c) for c in r["bbox"])
        for r in recs)


# -------------------------------------------------------------------- running

class Request:
    __slots__ = ("due", "body", "fire", "end", "status", "ok", "qwait", "doc")

    def __init__(self, due: float, body: int):
        self.due, self.body = due, body
        self.fire = self.end = self.qwait = self.doc = None
        self.status, self.ok = 0, False


def _send(sock: str, req: Request, bodies: list, num_classes: int,
          timeout: float) -> None:
    req.fire = time.monotonic()
    try:
        req.status, doc = http_unix(sock, "POST", "/predict",
                                    bodies[req.body], timeout=timeout)
    except (OSError, http.client.HTTPException):
        req.status, doc = 0, None
    req.end = time.monotonic()
    if req.status == 200 and well_formed(doc, num_classes):
        req.ok, req.doc = True, doc
        req.qwait = doc.get("queue_wait_ms")


def run_open(sock, offsets, order, bodies, num_classes, t0, timeout):
    """Fire each request at t0 + offset on the clock, one thread each."""
    reqs = [Request(t0 + o, b) for o, b in zip(offsets, order)]
    threads = []
    for r in reqs:
        lag = r.due - time.monotonic()
        if lag > 0:
            time.sleep(lag)
        th = threading.Thread(target=_send, daemon=True,
                              args=(sock, r, bodies, num_classes, timeout))
        th.start()
        threads.append(th)
    return reqs, threads


def run_closed(sock, clients, order, bodies, num_classes, t0, seconds,
               timeout):
    """``clients`` callers, each sending its next request when the last one
    answered.  A request is due when its client became free."""
    reqs, lock, nxt = [], threading.Lock(), [0]
    t_end = t0 + seconds

    def client():
        due = t0
        while True:
            now = time.monotonic()
            if now < due:
                time.sleep(due - now)
            if time.monotonic() >= t_end:
                return
            with lock:
                i = nxt[0]
                nxt[0] += 1
            r = Request(max(due, t0), order[i % len(order)])
            with lock:
                reqs.append(r)
            _send(sock, r, bodies, num_classes, timeout)
            due = r.end

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    for th in threads:
        th.start()
    return reqs, threads


def percentile(sorted_vals: list, q: float) -> float:
    """Nearest rank: the smallest value with at least q of the sample at or
    below it."""
    k = max(int(math.ceil(q * len(sorted_vals))) - 1, 0)
    return sorted_vals[k]


def summarize(reqs: list, t0: float, seconds: float, miss_ms: float,
              closed: bool = False) -> dict:
    """The window's numbers from its requests (all that were due in it).
    In a closed loop the rate counts the good responses that **ended** in
    the window: its clients always have a request in flight when the window
    closes, and counting those by their due time would add them all."""
    pop = [r for r in reqs if t0 <= r.due < t0 + seconds]
    lat = sorted((r.end - r.due) * 1e3 if r.ok else miss_ms for r in pop)
    late = sorted((r.fire - r.due) * 1e3 for r in pop if r.fire is not None)
    good = sum(1 for r in pop if r.ok)
    done = sum(1 for r in pop if r.ok and r.end < t0 + seconds) if closed \
        else good
    qw = [r.qwait for r in pop if r.ok and isinstance(r.qwait, (int, float))]
    status: dict = {}
    for r in pop:
        key = str(r.status) if (r.ok or r.status != 200) else "200_malformed"
        status[key] = status.get(key, 0) + 1
    return {
        "attempted": len(pop), "failed": len(pop) - good, "status": status,
        "serve_imgs_per_s": done / seconds,
        "serve_p50_ms": percentile(lat, 0.50) if lat else miss_ms,
        "serve_p95_ms": percentile(lat, 0.95) if lat else miss_ms,
        "loadgen_late_ms": percentile(late, 0.95) if late else None,
        "queue_wait_ms": sum(qw) / len(qw) if qw else None,
    }


def pick_sample(reqs: list, bodies: list, k: int, seed: int) -> list:
    """Indices into ``reqs`` of the finished requests to compare with the
    reference: ``k`` drawn from the seed, and the largest body among all."""
    done = [i for i, r in enumerate(reqs) if r.ok]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 4])
    pick = [int(i) for i in rng.choice(done, size=min(k, len(done)),
                                       replace=False)]
    longest = max(done, key=lambda i: (len(bodies[reqs[i].body]), -i))
    if longest not in pick:
        pick.append(longest)
    return pick


def warm(sock, bodies, sizes, num_classes, per_orientation, timeout):
    """Real bodies of both orientations, concurrently, until each has
    answered: host paths warm, both programs proven ready."""
    land = [i for i, (h, w) in enumerate(sizes) if w >= h]
    port = [i for i, (h, w) in enumerate(sizes) if w < h]
    reqs = [Request(time.monotonic(), (grp * per_orientation)[j])
            for grp in (land, port) if grp for j in range(per_orientation)]
    threads = [threading.Thread(target=_send, daemon=True,
                                args=(sock, r, bodies, num_classes, timeout))
               for r in reqs]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    bad = [r.status for r in reqs if not r.ok]
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad[:5]}")


def emit(doc: dict) -> None:
    sys.stdout.write(json.dumps(doc) + "\n")
    sys.stdout.flush()


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    traffic, sock, seed = spec["traffic"], spec["socket"], spec["seed"]
    seconds, k = float(spec["seconds"]), int(spec["num_classes"])
    drain_s = float(traffic.get("drain_s", 60.0))
    timeout = seconds + drain_s + 30.0
    mix = traffic["bodies"]
    sizes = body_sizes(mix)
    bodies = make_bodies(mix, seed)
    wait_ready(sock, float(spec.get("ready_limit_s", 1100.0)))
    warm(sock, bodies, sizes, k, int(traffic.get("warm_per_orientation", 16)),
         timeout)
    closed = traffic["arrival"] == "closed"
    if closed:
        order = body_order(4096, len(bodies), seed)
    else:
        offsets = arrival_offsets(traffic, seconds, seed)
        order = body_order(len(offsets), len(bodies), seed)
    t0 = time.monotonic() + 0.25
    emit({"event": "window", "t0": t0, "seconds": seconds})
    if closed:
        reqs, threads = run_closed(sock, int(traffic["clients"]), order,
                                   bodies, k, t0, seconds, timeout)
    else:
        reqs, threads = run_open(sock, offsets, order, bodies, k, t0, timeout)
    lag = t0 + seconds - time.monotonic()
    if lag > 0:
        time.sleep(lag)
    emit({"event": "closed", "t": time.monotonic()})
    t_stop = time.monotonic() + drain_s
    for th in threads:   # late is late, not wrong: wait out the drain
        th.join(max(t_stop - time.monotonic(), 0.0))
    reqs = list(reqs)
    for r in reqs:       # still in flight after the drain: never answered
        if r.end is None:
            r.end, r.ok, r.status = t_stop, False, 0
    out = summarize(reqs, t0, seconds, (seconds + drain_s) * 1e3, closed)
    pick = pick_sample(reqs, bodies, int(traffic.get("sample", 8)), seed)
    out["event"] = "result"
    # each sampled request with its whole response document: a
    # configuration's comparison may read more than the records' cls, score
    # and bbox ("detections" is the response's list, by its old name)
    out["sample"] = [{"body": reqs[i].body,
                      "doc": json.loads(bodies[reqs[i].body]),
                      "response": reqs[i].doc,
                      "detections": reqs[i].doc["detections"]} for i in pick]
    out["drained_s"] = time.monotonic() - (t0 + seconds)
    emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
