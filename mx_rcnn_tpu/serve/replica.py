"""Replica-side half of the multi-replica serving plane (ISSUE 8).

One replica = one supervised subprocess (``serve.py --replica-index I``)
pinned to a device or device group, running the ordinary
Predictor → ServeEngine → HTTP stack over a Unix socket the router
forwards to.  This module owns everything that happens INSIDE the
replica process:

* :func:`serve_replica` — the child's main loop: HTTP up FIRST (so
  liveness probes answer during a slow warmup), then warmup → ready,
  then park until SIGTERM.
* :func:`reload_engine_params` — the zero-downtime weight swap:
  drain → load → :meth:`Predictor.update_params` → canary probe →
  re-ready, with rollback to the previous weights when the new
  generation produces non-finite outputs on a golden image.  Because
  params are a RUNTIME argument to every registered program (PR-7
  registry), the swap reuses all compiled executables — zero
  steady-state recompiles, asserted by tests and the smoke script.
* :func:`scan_checkpoints` / :class:`CheckpointWatcher` — filesystem
  polling of the PR-2 checkpoint layout (``{prefix}/{epoch}`` +
  ``{prefix}/steps/{key}``), feeding reload targets to whoever rolls
  them (the supervisor across replicas, or the in-process path at
  ``--replicas 1``).
* :class:`ReplicaFaults` — the serve-side chaos harness: behavior is
  driven by ``MXR_FAULT_REPLICA_*`` env vars (the resilience.py
  ``MXR_FAULT_*`` precedent) so ``tests/test_replica.py`` injects
  kill -9 / hang / slow-start / corrupt-checkpoint without touching the
  code path under test.

Fault-injection env contract (each var is a comma-separated list of
``INDEX[:VALUE]`` tokens; a token applies to the replica whose
``--replica-index`` matches):

* ``MXR_FAULT_REPLICA_KILL_AFTER="0:5"``   — SIGKILL self (kill -9
  semantics) after 5 served 2xx requests.
* ``MXR_FAULT_REPLICA_HANG_AFTER="1:3"``   — wedge every subsequent
  HTTP handler (including probes) after 3 served requests: the
  crash-undetectable-by-waitpid case the supervisor's probe-timeout
  hang detection exists for.
* ``MXR_FAULT_REPLICA_SLOW_START_S="0:8"`` — sleep 8s between liveness
  and readiness (alive-but-warming), exercising the /healthz vs
  /readyz split.
* ``MXR_FAULT_REPLICA_CORRUPT_CKPT="0"``   — poison every float leaf of
  the next reloaded checkpoint with NaN, forcing the canary probe to
  reject the generation and roll back.

Network fault points (ISSUE 12) — same token grammar, applied at the
transport layer by :class:`NetFaults` so the fabric's chaos suite can
stage partitions, connection resets, and tail latency against real
sockets without touching the code under test:

* ``MXR_FAULT_NET_DROP="1:4"``      — after 4 ``/predict`` requests the
  member goes dark: EVERY handler (probes included) blackholes.  The
  router sees pure probe timeouts — a network partition, not a crash.
* ``MXR_FAULT_NET_RESET="0:3-6"``   — ``/predict`` requests number 3..6
  (1-based, inclusive; ``"0:3"`` means 3 onward forever) have their
  connections reset (RST) mid-handshake while probes stay healthy: the
  data-path-broken/control-path-fine case circuit breakers exist for.
  A bounded range lets the member RECOVER, closing the breaker.
* ``MXR_FAULT_NET_DELAY_MS="2:250"`` — every ``/predict`` response is
  delayed 250 ms: the slow-member tail that request hedging answers.
"""

from __future__ import annotations

import os
import signal
import threading
import time
from typing import Callable, Optional

import numpy as np

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.telemetry import tracectx
from mx_rcnn_tpu.data.loader import prepare_image
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.serve.frontend import make_server
from mx_rcnn_tpu.serve.warmup import warmup
from mx_rcnn_tpu.train.resilience import decode_step_key

ENV_KILL_AFTER = "MXR_FAULT_REPLICA_KILL_AFTER"
ENV_HANG_AFTER = "MXR_FAULT_REPLICA_HANG_AFTER"
ENV_SLOW_START = "MXR_FAULT_REPLICA_SLOW_START_S"
ENV_CORRUPT_CKPT = "MXR_FAULT_REPLICA_CORRUPT_CKPT"
ENV_NET_DROP = "MXR_FAULT_NET_DROP"
ENV_NET_RESET = "MXR_FAULT_NET_RESET"
ENV_NET_DELAY = "MXR_FAULT_NET_DELAY_MS"
# set by the supervisor on each child; the injectors match against it
ENV_REPLICA_INDEX = "MXR_REPLICA_INDEX"
# optional device pinning: the supervisor splits --replica-devices into
# per-child groups under this var; deployment images map it onto their
# platform's visibility env (TPU_VISIBLE_CHIPS / CUDA_VISIBLE_DEVICES)
ENV_REPLICA_DEVICES = "MXR_REPLICA_DEVICES"

# how long a drain may take before the reload aborts (the queue keeps
# flushing during drain, so this only trips on a wedged dispatcher)
RELOAD_DRAIN_TIMEOUT_S = 60.0


def _fault_value(env_name: str, index: int,
                 env=os.environ) -> Optional[str]:
    """The VALUE of the ``INDEX[:VALUE]`` token matching ``index`` in
    ``env_name`` ("" for a bare-INDEX token), or None."""
    for tok in env.get(env_name, "").split(","):
        tok = tok.strip()
        if not tok:
            continue
        idx, _, value = tok.partition(":")
        try:
            if int(idx) == index:
                return value
        except ValueError:
            logger.warning("bad %s token %r (want INDEX[:VALUE])",
                           env_name, tok)
    return None


class ReplicaFaults:
    """Parsed ``MXR_FAULT_REPLICA_*`` state for one replica index, wired
    into the frontend's ``request_hook``/``gate`` and the reload path.
    With no matching env tokens every method is a cheap no-op."""

    def __init__(self, index: int, env=os.environ):
        self.index = index

        def _num(name, cast):
            v = _fault_value(name, index, env)
            return None if v in (None, "") else cast(v)

        self.kill_after = _num(ENV_KILL_AFTER, int)
        self.hang_after = _num(ENV_HANG_AFTER, int)
        self.slow_start_s = _num(ENV_SLOW_START, float) or 0.0
        self.corrupt_ckpt = _fault_value(ENV_CORRUPT_CKPT, index,
                                         env) is not None
        self._served = 0
        self._hung = False
        self._lock = threading.Lock()

    def request_hook(self, status: int):
        """After each /predict reply: count 2xx and fire kill/hang once
        the configured count is reached."""
        with self._lock:
            if 200 <= status < 300:
                self._served += 1
            served = self._served
        if self.kill_after is not None and served >= self.kill_after:
            logger.warning("FAULT replica %d: SIGKILL self after %d "
                           "served requests", self.index, served)
            os.kill(os.getpid(), signal.SIGKILL)
        if self.hang_after is not None and served >= self.hang_after:
            self._hung = True

    def gate(self):
        """Before any HTTP handling: a hung replica wedges every handler
        thread — probes included — which is exactly what the supervisor's
        probe-timeout detection must catch (waitpid never fires)."""
        if self._hung:
            logger.warning("FAULT replica %d: hanging handler thread",
                           self.index)
            time.sleep(3600.0)

    def slow_start(self):
        if self.slow_start_s > 0:
            logger.warning("FAULT replica %d: slow start %.1fs (alive, "
                           "not ready)", self.index, self.slow_start_s)
            time.sleep(self.slow_start_s)


class NetFaults:
    """Parsed ``MXR_FAULT_NET_*`` state for one member index, wired into
    the frontend as ``net_faults`` (``intercept(path, handler)`` runs
    before any handling).  With no matching tokens, ``enabled`` is False
    and the frontend never calls in — zero cost on the clean path."""

    def __init__(self, index: int, env=os.environ):
        self.index = index

        def _num(name, cast):
            v = _fault_value(name, index, env)
            return None if v is None else cast(v) if v != "" else 0
        self.drop_after = _num(ENV_NET_DROP, int)
        self.delay_ms = _num(ENV_NET_DELAY, float) or 0.0
        self.reset_from = None
        self.reset_to = None
        reset = _fault_value(ENV_NET_RESET, index, env)
        if reset:
            lo, _, hi = reset.partition("-")
            self.reset_from = int(lo)
            self.reset_to = int(hi) if hi else None
        self._predicts = 0
        self._lock = threading.Lock()

    @property
    def enabled(self) -> bool:
        return (self.drop_after is not None or self.delay_ms > 0
                or self.reset_from is not None)

    def intercept(self, path: str, handler) -> bool:
        """True = the request was consumed by a fault (blackholed or
        reset); False = continue normal handling (possibly delayed)."""
        p = path.partition("?")[0]
        with self._lock:
            if p == "/predict":
                self._predicts += 1
            n = self._predicts
        if self.drop_after is not None and n > self.drop_after:
            # partition: the member is alive but unreachable — every
            # path (probes included) blackholes, so the router sees
            # probe timeouts, not errors
            logger.warning("FAULT net %d: blackholing %s (partition)",
                           self.index, p)
            time.sleep(3600.0)
            return True
        if p != "/predict":
            return False
        if (self.reset_from is not None and n >= self.reset_from
                and (self.reset_to is None or n <= self.reset_to)):
            logger.warning("FAULT net %d: resetting /predict #%d",
                           self.index, n)
            self._reset_connection(handler)
            return True
        if self.delay_ms > 0:
            time.sleep(self.delay_ms / 1e3)
        return False

    @staticmethod
    def _reset_connection(handler):
        """Abort the TCP connection with an RST (SO_LINGER 0) so the
        client sees ConnectionResetError — a broken data path, not a
        clean HTTP error."""
        import socket
        import struct
        handler.close_connection = True
        try:
            handler.connection.setsockopt(
                socket.SOL_SOCKET, socket.SO_LINGER,
                struct.pack("ii", 1, 0))
        except OSError:
            pass
        try:
            handler.connection.close()
        except OSError:
            pass


def poison_params(params):
    """The corrupt-checkpoint injection: NaN every float leaf (dict
    pytrees and bare numbers), leaving structure intact so the swap
    itself succeeds and only the CANARY catches it — the realistic
    bad-weights failure (half-written file, diverged training run)."""
    if isinstance(params, dict):
        return {k: poison_params(v) for k, v in params.items()}
    arr = np.asarray(params)
    if np.issubdtype(arr.dtype, np.floating):
        return np.full_like(arr, np.nan)
    return params


# -- checkpoint discovery (PR-2 layout, no orbax import) -------------------

def _committed_dir(path: str) -> bool:
    """True when an int-named checkpoint dir holds at least one
    committed (non-tmp) entry.  A trainer killed mid-save can leave the
    dir itself behind empty, or holding only ``*tmp*`` payload still
    being staged — selecting either would hand the watcher a target
    whose load fails and lands on the bad list, burning the generation.
    The dir vanishing between listdir and this check (concurrent
    cleanup) is just not-committed."""
    try:
        names = os.listdir(path)
    except OSError:
        return False
    return any("tmp" not in n for n in names)


def scan_checkpoints(prefix: str) -> Optional[dict]:
    """Newest committed checkpoint under ``prefix`` as a reload target
    ``{"prefix", "kind", "epoch", "consumed"}`` — epoch dirs
    ``{prefix}/{E}`` and step dirs ``{prefix}/steps/{E*1e7+C}``, the
    furthest position winning exactly like ``latest_resume_point`` (a
    finished epoch beats its own mid-epoch saves).  Pure listdir — orbax
    commits by atomic rename, so an int-named dir is a committed save
    and in-progress ``*.orbax-checkpoint-tmp*`` names never int-parse;
    :func:`_committed_dir` additionally skips the husk a trainer killed
    mid-save leaves behind (empty or tmp-only int dir)."""
    if not os.path.isdir(prefix):
        return None
    cands = []
    for name in os.listdir(prefix):
        try:
            e = int(name)
        except ValueError:
            continue
        p = os.path.join(prefix, name)
        if os.path.isdir(p) and _committed_dir(p):
            cands.append((e, 0, "epoch"))
    steps_dir = os.path.join(prefix, "steps")
    if os.path.isdir(steps_dir):
        for name in os.listdir(steps_dir):
            try:
                key = int(name)
            except ValueError:
                continue
            p = os.path.join(steps_dir, name)
            if os.path.isdir(p) and _committed_dir(p):
                e, c = decode_step_key(key)
                cands.append((e, c, "step"))
    if not cands:
        return None
    e, c, kind = max(cands)
    return {"prefix": prefix, "kind": kind, "epoch": e, "consumed": c}


def target_key(target: dict) -> tuple:
    """Identity of a reload target for dedup/bad-list bookkeeping."""
    return (target["epoch"], target["consumed"], target["kind"])


def load_serving_params(target: dict, cfg):
    """Load a reload target's params DENORMALIZED for inference: epoch
    checkpoints via ``load_epoch(for_training=False)``; step checkpoints
    hold the RAW training parametrization, so the live-training-tracking
    path must apply ``denormalize_for_save`` itself or served boxes
    would decode against folded bbox stats."""
    from mx_rcnn_tpu.train.checkpoint import (CheckpointManager,
                                              denormalize_for_save)

    mgr = CheckpointManager(target["prefix"])
    if target["kind"] == "step":
        payload = mgr.load_step_checkpoint(target["epoch"],
                                           target["consumed"])
        return denormalize_for_save(payload["params"], cfg)
    params, _, _ = mgr.load_epoch(target["epoch"], cfg, for_training=False)
    return params


class CheckpointWatcher:
    """Polls a checkpoint prefix and fires ``reload_fn(target)`` when a
    NEWER generation appears.  Failed targets (load error, canary
    rejection) go on a bad list and are never retried — a corrupt save
    must not flap the plane; the next good save supersedes it.
    ``poll_once`` is the injectable-clock-style test surface; ``start``
    wraps it in a daemon thread for production."""

    def __init__(self, prefix: str, reload_fn: Callable[[dict], bool],
                 interval_s: float = 5.0, scan_fn=None):
        self.prefix = prefix
        self.reload_fn = reload_fn
        self.interval_s = interval_s
        self._scan = scan_fn or scan_checkpoints
        self._last: Optional[tuple] = None
        self._bad: set = set()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def prime(self):
        """Mark whatever is on disk NOW as already-served (the weights
        the replicas booted from) so the first poll doesn't redundantly
        reload the boot checkpoint onto itself."""
        tgt = self._scan(self.prefix)
        if tgt is not None:
            self._last = target_key(tgt)
        return tgt

    def poll_once(self):
        """One scan→maybe-reload step.  Returns None when nothing new,
        else ``(target, ok)``."""
        tgt = self._scan(self.prefix)
        if tgt is None:
            return None
        key = target_key(tgt)
        if key == self._last or key in self._bad:
            return None
        if self._last is not None and key < self._last:
            return None  # never roll BACKWARD off a stale dir listing
        logger.info("checkpoint watcher: new generation %s under %s",
                    key, self.prefix)
        ok = bool(self.reload_fn(tgt))
        if ok:
            self._last = key
        else:
            self._bad.add(key)
            telemetry.get().counter("replica/reload_bad_target")
            logger.warning("checkpoint watcher: target %s rejected — "
                           "skipping it until a newer save appears", key)
        return tgt, ok

    def start(self) -> "CheckpointWatcher":
        assert self._thread is None, "watcher already started"
        self.prime()

        def loop():
            while not self._stop.wait(self.interval_s):
                try:
                    self.poll_once()
                except Exception:  # noqa: BLE001 — keep watching
                    logger.exception("checkpoint watcher poll failed")

        self._thread = threading.Thread(target=loop, name="ckpt-watcher",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None


# -- the hot swap ----------------------------------------------------------

def golden_image(h: int, w: int) -> np.ndarray:
    """Deterministic canary input: a horizontal gradient (not zeros —
    constant inputs can hide scale-dependent blowups)."""
    row = np.linspace(32, 224, w).astype(np.uint8)
    return np.ascontiguousarray(
        np.broadcast_to(row[None, :, None], (h, w, 3)))


def canary_probe(engine, predictor) -> tuple:
    """Forward a golden batch at the WARMED landscape bucket shape and
    check every float output is finite — the cheap, recompile-free
    weights-sanity gate a new generation must pass before it serves.
    Probes the SAME program the engine dispatches — the fused
    ``serve_e2e`` program when the engine runs single-dispatch mode, the
    legacy forward otherwise — so the probe never first-dispatches a
    program warmup didn't register (which would break the
    ``recompiles_during_swap == 0`` pin).  Returns (ok, reason)."""
    short, long_ = engine._scale
    B = engine.opts.batch_size
    if getattr(engine.opts, "serve_e2e", False):
        from mx_rcnn_tpu.data.image import stage_raw_to_bucket

        cfg = engine.cfg
        staged, raw_hw, ratio, info = stage_raw_to_bucket(
            golden_image(short, long_), engine._scale,
            max(cfg.network.IMAGE_STRIDE, cfg.network.RPN_FEAT_STRIDE))
        dets, _ = predictor.predict_serve_e2e(
            np.stack([staged] * B), np.stack([raw_hw] * B),
            np.asarray([ratio] * B, np.float32),
            np.stack([info] * B).astype(np.float32),
            np.zeros(B, bool),
            int(cfg.TEST.MAX_PER_IMAGE), float(cfg.TEST.THRESH))
        if not np.isfinite(np.asarray(dets)).all():
            return False, "non-finite detections on golden image"
        return True, "ok"
    prepared, im_info = prepare_image(golden_image(short, long_),
                                      engine.cfg, engine._scale)
    images = np.stack([prepared] * B)
    infos = np.stack([im_info] * B)
    out = predictor.predict(images, infos)
    names = ("rois", "roi_valid", "cls_prob", "bbox_deltas")
    for name, arr in zip(names, out[:len(names)]):
        arr = np.asarray(arr)
        if (np.issubdtype(arr.dtype, np.floating)
                and not np.isfinite(arr).all()):
            return False, f"non-finite {name} on golden image"
    return True, "ok"


def reload_engine_params(engine, predictor, cfg, target: dict,
                         load_params_fn=None, faults=None) -> tuple:
    """The zero-downtime swap on one engine: drain → load → swap →
    canary → resume.  Returns ``(ok, info)``; on any failure the
    previous weights are restored verbatim (the exact pre-swap leaves,
    so rollback itself is also recompile-free) and the engine resumes
    serving them.  ``info["recompiles_during_swap"]`` pins the PR-7
    registry-reuse contract: 0 in steady state.

    A target carrying ``eval_shard`` (the fleet promotion gate, ISSUE
    17) additionally must BEAT the incumbent: the incumbent's mean
    detection agreement over the held-out shard is measured before the
    swap, the candidate's after, and a candidate scoring below
    ``incumbent - quality_slack`` is rolled back exactly like a canary
    failure — the PR-8 "finite outputs" canary extended to a measured
    quality delta.  An unreadable eval shard fails CLOSED (no swap at
    all).  The generation only advances on acceptance, so a rejected
    candidate can be retried by a later, better save.  The fabric
    unroutes a member for the whole reload, so gate probes are the only
    requests the candidate ever answers on a rejected promotion."""
    tel = telemetry.get()
    t0 = time.monotonic()
    gen = int(target.get("generation", engine.generation + 1))
    shard = quality_incumbent = None
    if target.get("eval_shard"):
        from mx_rcnn_tpu.flywheel.fleet import (eval_shard_quality,
                                                load_eval_shard)
        try:
            shard = load_eval_shard(target["eval_shard"])
        except (OSError, ValueError, KeyError) as e:
            tel.counter("flywheel/promotion_gate_reject")
            tel.dump_flight("promotion_rejected", generation=gen,
                            target=list(target_key(target)),
                            cause=f"eval shard unreadable: {e}",
                            trace_ids=target.get("trace_ids") or [])
            logger.error("promotion of %s REJECTED: eval shard "
                         "unreadable (%s) — gate fails closed",
                         target_key(target), e)
            return False, {"error": f"eval shard unreadable: {e}",
                           "rolled_back": False}
        quality_incumbent = eval_shard_quality(engine, shard)
    if not engine.drain(timeout=RELOAD_DRAIN_TIMEOUT_S):
        engine.resume()
        return False, {"error": "drain timed out — dispatcher wedged?",
                       "rolled_back": False}
    old = getattr(predictor, "params", None)
    recompiles_before = engine.counters["recompiles"]
    try:
        load = load_params_fn or load_serving_params
        params = load(target, cfg)
        if faults is not None and faults.corrupt_ckpt:
            logger.warning("FAULT: poisoning reloaded checkpoint %s with "
                           "NaN", target_key(target))
            params = poison_params(params)
        predictor.update_params(params)
        ok, reason = canary_probe(engine, predictor)
        if not ok:
            predictor.params = old  # rollback: pre-swap leaves, no cast
            tel.counter("serve/reload_rollback")
            tel.dump_flight("reload_canary_failed", generation=gen,
                            target=list(target_key(target)), cause=reason)
            logger.error("hot reload of %s REJECTED (%s) — rolled back "
                         "to generation %d", target_key(target), reason,
                         engine.generation)
            return False, {"error": f"canary failed: {reason}",
                           "rolled_back": True}
    except Exception as e:  # noqa: BLE001 — a bad save must not kill serving
        if old is not None:
            predictor.params = old
        tel.counter("serve/reload_rollback")
        logger.exception("hot reload of %s failed — rolled back",
                         target_key(target))
        return False, {"error": f"{type(e).__name__}: {e}",
                       "rolled_back": True}
    finally:
        engine.resume()
    quality_candidate = None
    if shard is not None:
        from mx_rcnn_tpu.flywheel.fleet import eval_shard_quality
        slack = float(target.get("quality_slack", 0.0))
        quality_candidate = eval_shard_quality(engine, shard)
        if quality_candidate + 1e-9 < quality_incumbent - slack:
            engine.drain(timeout=RELOAD_DRAIN_TIMEOUT_S)
            try:
                if old is not None:
                    predictor.params = old
            finally:
                engine.resume()
            tel.counter("serve/reload_rollback")
            tel.counter("flywheel/promotion_gate_reject")
            tel.dump_flight("promotion_rejected", generation=gen,
                            target=list(target_key(target)),
                            quality_candidate=round(quality_candidate, 4),
                            quality_incumbent=round(quality_incumbent, 4),
                            quality_slack=slack,
                            trace_ids=target.get("trace_ids") or [])
            logger.error("promotion of %s REJECTED by quality gate "
                         "(candidate %.4f < incumbent %.4f - slack %.4f)"
                         " — rolled back to generation %d",
                         target_key(target), quality_candidate,
                         quality_incumbent, slack, engine.generation)
            return False, {"error": "quality gate: candidate %.4f < "
                                    "incumbent %.4f - slack %.4f"
                                    % (quality_candidate,
                                       quality_incumbent, slack),
                           "rolled_back": True,
                           "quality_candidate": quality_candidate,
                           "quality_incumbent": quality_incumbent}
        tel.counter("flywheel/promotion_gate_pass")
    with engine._lock:
        engine.generation = max(engine.generation, gen)
    swap_recompiles = engine.counters["recompiles"] - recompiles_before
    tel.counter("serve/reload")
    tel.gauge("serve/generation", engine.generation)
    wall = time.monotonic() - t0
    logger.info("hot reload: generation %d live from %s in %.2fs "
                "(%d recompile(s) during swap)", engine.generation,
                target_key(target), wall, swap_recompiles)
    info = {"generation": engine.generation,
            "target": list(target_key(target)),
            "wall_s": round(wall, 3),
            "recompiles_during_swap": swap_recompiles}
    if shard is not None:
        info["quality_candidate"] = quality_candidate
        info["quality_incumbent"] = quality_incumbent
    return True, info


def make_reloader(engine, predictor, cfg, load_params_fn=None,
                  faults=None):
    """The frontend's ``POST /admin/reload`` callback: body is a reload
    target doc, 200 → new generation live, 409 → rejected + rolled
    back.  Serialized — concurrent reloads of one replica make no
    sense and would race the drain."""
    lock = threading.Lock()

    def reloader(doc: dict) -> tuple:
        required = {"prefix", "kind", "epoch", "consumed"}
        if not required.issubset(doc):
            return 400, {"error": f"reload target needs {sorted(required)}"}
        with lock:
            ok, info = reload_engine_params(
                engine, predictor, cfg, doc,
                load_params_fn=load_params_fn, faults=faults)
        return (200 if ok else 409), info

    return reloader


# -- the child main loop ---------------------------------------------------

def serve_replica(engine, cfg, sock_path: Optional[str] = None,
                  index: int = 0, predictor=None, load_params_fn=None,
                  done: Optional[threading.Event] = None,
                  port: Optional[int] = None, host: str = "127.0.0.1",
                  join: Optional[str] = None,
                  advertise: Optional[str] = None) -> None:
    """Run one replica to completion: HTTP server FIRST (liveness probes
    must answer while warmup compiles), then warmup → ready, then park
    until ``done`` (set by the driver's signal handler) — finally stop
    the server and fail whatever is still queued.  The engine must be
    ``start()``ed; ``predictor`` defaults to ``engine.predictor``.

    Transport is ``sock_path`` (a fork child behind the PR-8 supervisor)
    OR ``port``/``host`` (a fabric member on TCP).  ``join`` registers
    the member with a fabric router at that address once warm,
    advertising ``advertise`` (default ``host:port``)."""
    predictor = predictor if predictor is not None else engine.predictor
    # subprocess members inherit tracing opt-in via MXR_TRACE_DIR — a
    # no-op when the env is absent or the parent already configured one
    tracectx.configure_from_env(member=f"member{index}", rank=index)
    faults = ReplicaFaults(index)
    net = NetFaults(index)
    reloader = make_reloader(engine, predictor, cfg,
                             load_params_fn=load_params_fn, faults=faults)
    server = make_server(engine, unix_socket=sock_path, port=port,
                         host=host, reloader=reloader,
                         request_hook=faults.request_hook,
                         gate=faults.gate,
                         net_faults=net if net.enabled else None)
    th = threading.Thread(target=server.serve_forever,
                          name=f"replica-{index}-http", daemon=True)
    th.start()
    where = sock_path if sock_path is not None else f"{host}:{port}"
    logger.info("replica %d: live on %s (warming)", index, where)
    faults.slow_start()
    warmup(engine)  # sets engine readiness → /readyz flips to 200
    logger.info("replica %d: ready (generation %d)", index,
                engine.generation)
    join_stop = None
    if join:
        from mx_rcnn_tpu.serve.fabric import register_with_router
        join_stop = register_with_router(
            join, advertise or f"{host}:{port}")
    done = done or threading.Event()
    done.wait()
    if join_stop is not None:
        join_stop.set()
    server.shutdown()
    engine.stop()
