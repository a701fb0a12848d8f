"""Multi-model serving plane: one device, a fleet of models.

``serve.py`` historically bound one process to one ``(config, params,
Predictor)``.  :class:`ModelPool` lifts that to N models behind a single
frontend without N× device memory, N× recompiles, or one tenant's burst
destroying another's p99:

* **Model registry.**  Each entry keys a model id to its own config,
  ``Predictor`` (hence its own ``ProgramRegistry`` — program identity
  already folds the config digest, so models get disjoint program keys
  and AOT cache subtrees for free) and its own :class:`ServeEngine`
  started in external-dispatch mode.  ``/predict?model=...`` resolves
  here; requests without a model land on the default entry, preserving
  single-model semantics byte-for-byte.
* **Device weight residency.**  Param trees are paged host↔device under
  a configurable byte budget (``--weight-budget-mb``) with LRU eviction
  over last-dispatch time.  A page-out snapshots the variant-cast tree
  to host memory and deletes the device buffers; a page-in is a plain
  ``device_put`` of that snapshot — params are RUNTIME arguments to
  every registered program (the ``update_params`` hot-reload contract),
  so paging costs zero recompiles.  Pinned models are never paged out
  (their registries are also exempt from program LRU eviction).
  Counters ``serve/weight_page_in|out`` + per-model residency gauges
  make the paging observable on ``/metrics``.
* **Cross-model batch scheduling.**  ONE pool dispatcher thread owns
  the device and interleaves per-model bucket queues: among models with
  a due flush it picks the highest ``weight * (queue_depth + 1)`` score
  (weight = the model's SLO class), tie-broken by least-recently
  scheduled, so heterogeneous traffic keeps dispatch occupancy high and
  a cheap model is not starved by a heavy one.  Within a model the
  engine's own full-beats-oldest-partial bucket ordering is unchanged.
* **Tenant isolation.**  Each entry can carry its own
  :class:`~mx_rcnn_tpu.serve.controller.SLOController` (distinct
  ``--target-p99-ms``): admission shedding and flush-policy adaptation
  act on that model's engine only, so a burst on the mask model sheds
  the mask model's traffic first.

Driver: ``serve.py --models a=resnet50,b=vgg16`` (per-model overrides
via ``--model-arg``); pinned by ``tests/test_multimodel.py`` (routing,
paging under a budget, cross-model scheduling, two real models).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.telemetry import Hist, tracectx

# tenant fidelity classes (--model-arg ID:fidelity=...): "cascade" routes
# the tenant's traffic through the confidence gate, "full" pins it to the
# big model unconditionally — the SLO escape hatch for tenants whose
# accuracy budget admits no small-model answers
FIDELITY_CLASSES = ("cascade", "full")


def param_nbytes(tree) -> int:
    """Total bytes of a param tree's leaves (device or host)."""
    try:
        import jax

        leaves = jax.tree_util.tree_leaves(tree)
    except Exception:
        return 0
    total = 0
    for leaf in leaves:
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None:
            size = getattr(leaf, "size", 0)
            itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", 0)
            nbytes = size * itemsize
        total += int(nbytes)
    return total


class ModelEntry:
    """One registered model: identity, compute, policy, residency."""

    __slots__ = ("model_id", "cfg", "predictor", "engine", "controller",
                 "pinned", "weight", "fidelity", "resident", "bytes",
                 "host_params", "last_use", "last_sched", "batches",
                 "page_ins", "page_outs")

    def __init__(self, model_id, cfg, predictor, engine, controller=None,
                 pinned=False, weight=1.0, fidelity="cascade"):
        self.model_id = model_id
        self.cfg = cfg
        self.predictor = predictor
        self.engine = engine
        self.controller = controller
        self.pinned = bool(pinned)
        self.weight = max(float(weight), 1e-3)
        if fidelity not in FIDELITY_CLASSES:
            raise ValueError(f"fidelity must be one of {FIDELITY_CLASSES}, "
                             f"got {fidelity!r}")
        self.fidelity = fidelity
        self.resident = True        # params arrive placed by construction
        self.bytes = param_nbytes(getattr(predictor, "params", None))
        self.host_params = None     # host snapshot while paged out
        self.last_use = time.monotonic()
        self.last_sched = 0.0
        self.batches = 0
        self.page_ins = 0
        self.page_outs = 0


class ModelPool:
    """Owns the model entries, the weight-residency manager, and the one
    cross-model dispatcher thread.  Engines must be started with
    ``start(external=True)`` before :meth:`add_model`."""

    def __init__(self, budget_bytes: int = 0, idle_poll_s: float = 0.05):
        # 0 = unbounded (no paging ever happens except explicit calls)
        self.budget_bytes = max(int(budget_bytes), 0)
        self._idle_poll_s = max(float(idle_poll_s), 1e-3)
        self._entries: "Dict[str, ModelEntry]" = {}
        self._order: List[str] = []     # registration order; [0] = default
        self._lock = threading.RLock()
        self._wake = threading.Event()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._last_model: Optional[str] = None
        self.counters = {"weight_page_in": 0, "weight_page_out": 0,
                         "sched_batches": 0, "sched_switches": 0}
        # CascadeRouter, when --cascade is configured; /metrics grows a
        # "cascade" section.  The pool never calls into it — the router
        # sits a layer above the scheduler (its escalations arrive as
        # ordinary big-model submits the dispatcher interleaves).
        self.cascade = None

    # -- registry --------------------------------------------------------

    def add_model(self, model_id: str, cfg, predictor, engine,
                  controller=None, pinned: bool = False,
                  weight: float = 1.0,
                  fidelity: str = "cascade") -> ModelEntry:
        if not model_id or "/" in model_id:
            raise ValueError(f"bad model id {model_id!r}")
        entry = ModelEntry(model_id, cfg, predictor, engine,
                           controller=controller, pinned=pinned,
                           weight=weight, fidelity=fidelity)
        with self._lock:
            if model_id in self._entries:
                raise ValueError(f"model {model_id!r} already registered")
            if pinned:
                pinned_total = entry.bytes + sum(
                    e.bytes for e in self._entries.values() if e.pinned)
                if self.budget_bytes and pinned_total > self.budget_bytes:
                    raise ValueError(
                        f"pinned models need {pinned_total} bytes, over "
                        f"the {self.budget_bytes}-byte weight budget")
                reg = getattr(predictor, "registry", None)
                if reg is not None:
                    reg.pinned = True
            self._entries[model_id] = entry
            self._order.append(model_id)
        engine.on_work = self._wake.set
        # a new resident model may push the pool over budget: evict
        # colder models rather than refusing the registration
        self.ensure_resident(model_id)
        logger.info("model pool: registered %r (%d bytes, pinned=%s, "
                    "weight=%g)", model_id, entry.bytes, pinned, weight)
        return entry

    def model_ids(self) -> List[str]:
        with self._lock:
            return list(self._order)

    @property
    def default_model(self) -> Optional[str]:
        with self._lock:
            return self._order[0] if self._order else None

    def entry(self, model_id: Optional[str] = None) -> ModelEntry:
        """Resolve a model id (None = default) to its entry; raises
        ``KeyError`` for unknown ids — the frontend's 404."""
        with self._lock:
            if model_id is None:
                if not self._order:
                    raise KeyError("model pool is empty")
                model_id = self._order[0]
            e = self._entries.get(model_id)
            if e is None:
                raise KeyError(f"unknown model {model_id!r} "
                               f"(have {sorted(self._entries)})")
            return e

    def engine_for(self, model_id: Optional[str] = None):
        return self.entry(model_id).engine

    # -- lifecycle -------------------------------------------------------

    def start(self) -> "ModelPool":
        if self._thread is not None:
            return self
        self._stop = False
        self._thread = threading.Thread(target=self._dispatch_loop,
                                        name="pool-dispatch", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0):
        with self._lock:
            entries = list(self._entries.values())
        for e in entries:
            if e.controller is not None:
                try:
                    e.controller.stop()
                except Exception:
                    pass
            e.engine.stop(timeout=timeout)
        self._stop = True
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None

    def is_ready(self) -> bool:
        with self._lock:
            entries = list(self._entries.values())
        return bool(entries) and all(e.engine.is_ready() for e in entries)

    def readiness(self) -> dict:
        with self._lock:
            entries = [(mid, self._entries[mid]) for mid in self._order]
        per_model = {mid: e.engine.readiness() for mid, e in entries}
        return {"ready": bool(per_model)
                and all(d["ready"] for d in per_model.values()),
                "models": per_model}

    # -- weight residency ------------------------------------------------

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(e.bytes for e in self._entries.values()
                       if e.resident)

    def ensure_resident(self, model_id: str) -> None:
        """Make ``model_id``'s params device-resident, paging out LRU
        non-pinned siblings as needed to respect the byte budget.  Called
        by the dispatcher before every batch; cheap no-op when already
        resident (the steady state)."""
        with self._lock:
            e = self._entries[model_id]
            e.last_use = time.monotonic()
            if e.resident:
                self._evict_over_budget_locked(keep=model_id)
                return
            need = e.bytes
            if self.budget_bytes:
                self._evict_over_budget_locked(keep=model_id, incoming=need)
            self._page_in_locked(e)

    def _evict_over_budget_locked(self, keep: str, incoming: int = 0):
        if not self.budget_bytes:
            return
        resident = sum(e.bytes for e in self._entries.values()
                       if e.resident)
        over = resident + incoming - self.budget_bytes
        if over <= 0:
            return
        victims = sorted(
            (e for e in self._entries.values()
             if e.resident and not e.pinned and e.model_id != keep),
            key=lambda e: e.last_use)
        for v in victims:
            if over <= 0:
                break
            self._page_out_locked(v)
            over -= v.bytes
        if over > 0:
            # pinned + the incoming model alone exceed the budget; serve
            # anyway (refusing would deadlock traffic) but say so loudly
            logger.warning("model pool: weight budget %d bytes exceeded "
                           "by %d bytes even after paging (pinned set too "
                           "large?)", self.budget_bytes, over)

    def _page_out_locked(self, e: ModelEntry):
        import numpy as np

        params = getattr(e.predictor, "params", None)
        if params is None:
            e.resident = False
            return
        try:
            import jax

            host = jax.tree_util.tree_map(
                lambda x: np.array(x, copy=True), jax.device_get(params))
            for leaf in jax.tree_util.tree_leaves(params):
                delete = getattr(leaf, "delete", None)
                if delete is not None:
                    try:
                        delete()
                    except Exception:
                        pass
        except Exception:
            host = params  # duck-typed predictor: host tree already
        # host tree stays bound: an unscheduled dispatch would still be
        # CORRECT (jax transfers arguments), just unaccounted — the
        # dispatcher's ensure_resident keeps the hot path paged in
        e.predictor.params = host
        e.host_params = host
        e.resident = False
        e.page_outs += 1
        self.counters["weight_page_out"] += 1
        telemetry.get().counter("serve/weight_page_out")
        logger.info("model pool: paged OUT %r (%d bytes)", e.model_id,
                    e.bytes)

    def _page_in_locked(self, e: ModelEntry):
        host = e.host_params if e.host_params is not None \
            else getattr(e.predictor, "params", None)
        if host is not None:
            try:
                import jax

                plan = getattr(e.predictor, "plan", None)
                placed = (jax.device_put(host, plan.replicated())
                          if plan is not None else jax.device_put(host))
            except Exception:
                placed = host  # duck-typed predictor
            e.predictor.params = placed
            e.bytes = param_nbytes(placed) or e.bytes
        e.host_params = None
        e.resident = True
        e.page_ins += 1
        self.counters["weight_page_in"] += 1
        telemetry.get().counter("serve/weight_page_in")
        logger.info("model pool: paged IN %r (%d bytes)", e.model_id,
                    e.bytes)

    def residency(self) -> dict:
        """The /metrics residency doc: budget, live device bytes, and a
        per-model gauge block (also mirrored into the telemetry sink as
        ``serve/resident_bytes`` + ``serve/resident/<model>``)."""
        now = time.monotonic()
        with self._lock:
            models = {
                e.model_id: {"resident": int(e.resident),
                             "bytes": e.bytes,
                             "pinned": e.pinned,
                             "weight": e.weight,
                             "page_ins": e.page_ins,
                             "page_outs": e.page_outs,
                             "idle_s": round(now - e.last_use, 3)}
                for e in self._entries.values()}
            device_bytes = sum(e.bytes for e in self._entries.values()
                               if e.resident)
        tel = telemetry.get()
        tel.gauge("serve/resident_bytes", device_bytes)
        for mid, doc in models.items():
            tel.gauge(f"serve/resident/{mid}", doc["resident"])
        return {"budget_bytes": self.budget_bytes,
                "device_bytes": device_bytes,
                "resident_models": sum(d["resident"]
                                       for d in models.values()),
                "models": models}

    def demand_scores(self) -> dict:
        """``{model_id: weight * (queue_depth + 1)}`` — the scheduler's
        own scoring, exported as the autoscaler's placement signal
        (ISSUE 18)."""
        with self._lock:
            entries = list(self._entries.values())
        scores = {}
        for e in entries:
            try:
                depth = e.engine.queue_depth()
            except Exception:  # noqa: BLE001 — engine mid-shutdown
                depth = 0
            scores[e.model_id] = e.weight * (depth + 1)
        return scores

    def rebalance_residency(self) -> List[str]:
        """Runtime placement for the capacity authority: page the
        hottest queued-but-not-resident models in ahead of their next
        dispatch (``ensure_resident`` pages out cold LRU siblings to
        make room).  Paging is a ``device_put`` of a host snapshot —
        params are runtime args, so placement costs zero recompiles.
        Returns the model ids paged in (empty in the steady state, and
        always empty without a byte budget: everything is resident)."""
        with self._lock:
            cold = [(e.model_id, e.weight, e.engine)
                    for e in self._entries.values() if not e.resident]
        hot = []
        for mid, weight, engine in cold:
            try:
                depth = engine.queue_depth()
            except Exception:  # noqa: BLE001 — engine mid-shutdown
                continue
            if depth > 0:
                hot.append((weight * (depth + 1), mid))
        paged = []
        for _, mid in sorted(hot, reverse=True):
            try:
                self.ensure_resident(mid)
                paged.append(mid)
            except KeyError:
                continue  # removed mid-rebalance
        return paged

    # -- cross-model dispatch --------------------------------------------

    def _pick_locked(self, now: float):
        """(entry, wait_s): the due model with the best
        ``weight * (depth + 1)`` score (least-recently-scheduled breaks
        ties), or (None, soonest-deadline) when nothing is due."""
        best = None
        best_score = None
        wait = None
        for mid in self._order:
            e = self._entries[mid]
            due, depth, w = e.engine.due_state(now)
            if due:
                score = (e.weight * (depth + 1), -e.last_sched)
                if best is None or score > best_score:
                    best, best_score = e, score
            elif w is not None:
                wait = w if wait is None else min(wait, w)
        return best, wait

    def _dispatch_loop(self):
        while not self._stop:
            now = time.monotonic()
            with self._lock:
                e, wait = self._pick_locked(now)
            if e is None:
                timeout = self._idle_poll_s if wait is None \
                    else max(min(wait, self._idle_poll_s), 1e-4)
                self._wake.wait(timeout=timeout)
                self._wake.clear()
                continue
            batch, _ = e.engine.poll(now)
            if batch is None:
                # raced with a sweep/policy change; re-judge immediately
                continue
            tracer = tracectx.get()
            t_sched = time.perf_counter() if tracer.enabled else 0.0
            self.ensure_resident(e.model_id)
            with self._lock:
                switched = self._last_model not in (None, e.model_id)
                if switched:
                    self.counters["sched_switches"] += 1
                self._last_model = e.model_id
                e.last_sched = now
                e.batches += 1
                self.counters["sched_batches"] += 1
            if tracer.enabled:
                # pool/sched span per traced request in the claimed
                # batch: which model the interleaver picked, whether the
                # pick switched programs, and what residency paging cost
                # the batch paid before its dispatch
                sched_s = time.perf_counter() - t_sched
                for r in batch:
                    ctx = r.trace
                    if ctx is not None and ctx.sampled:
                        tracer.record(ctx, "pool/sched", sched_s,
                                      attrs={"model": e.model_id,
                                             "switched": switched,
                                             "batch": len(batch)})
            e.engine.dispatch_batch(batch)

    # -- introspection ---------------------------------------------------

    def metrics(self) -> dict:
        """The pool-mode ``/metrics`` payload.  Top-level ``counters``
        aggregates every model's engine counters (so single-model
        clients — a generator's server-counter deltas — keep working), with
        the full per-model picture under ``models`` and the pool's own
        scheduling + residency state alongside."""
        with self._lock:
            order = list(self._order)
            pool_counters = dict(self.counters)
            batches = {mid: self._entries[mid].batches for mid in order}
        models = {mid: self.engine_for(mid).metrics() for mid in order}
        agg: Dict[str, float] = {}
        for doc in models.values():
            for k, v in (doc.get("counters") or {}).items():
                if isinstance(v, (int, float)):
                    agg[k] = agg.get(k, 0) + v
        out = {"multimodel": True,
               "default_model": order[0] if order else None,
               "models": models,
               "counters": agg,
               "queue_depth": sum(d.get("queue_depth", 0)
                                  for d in models.values()),
               "ready": bool(models) and all(d.get("ready")
                                             for d in models.values()),
               "pool": {"counters": pool_counters,
                        "batches": batches,
                        "last_model": self._last_model},
               "residency": self.residency()}
        if self.cascade is not None:
            out["cascade"] = self.cascade.metrics()
        return out


# ---------------------------------------------------------------------------
# Cascade serving (ISSUE 19): cheap model first, escalate the hard frames.


class CascadeFuture:
    """Completion handle for one cascade-routed request.

    Duck-compatible with :class:`~mx_rcnn_tpu.serve.engine.ServeFuture`
    (``result`` / ``done`` / ``queue_wait_s`` / ``_error``) so the
    frontend and the stream layer can hold either.  In ``gate`` mode the
    escalation decision is taken exactly once, on the first ``result``
    call, from the hardness the on-device gate stamped on the small
    model's future — concurrent resolvers agree on one decision and one
    escalated submit.
    """

    __slots__ = ("_router", "_fut", "_mode", "_model", "_reason",
                 "_deadline_ms", "_lock", "_decided", "_escalated",
                 "_big_fut", "_counted_big")

    def __init__(self, router, fut, mode, model, reason=None,
                 deadline_ms=None):
        self._router = router
        self._fut = fut              # small fut (gate) or the final fut
        self._mode = mode            # "gate" | "direct"
        self._model = model
        self._reason = reason
        self._deadline_ms = deadline_ms
        self._lock = threading.Lock()
        self._decided = False
        self._escalated = False
        self._big_fut = None
        self._counted_big = False

    def done(self) -> bool:
        with self._lock:
            big = self._big_fut
            decided = self._decided
        if big is not None:
            return big.done()
        if self._mode == "direct" or decided:
            return self._fut.done()
        return False  # gate verdict pending — result() takes it

    @property
    def _error(self):
        with self._lock:
            big = self._big_fut
        src = big if big is not None else self._fut
        return getattr(src, "_error", None)

    @property
    def queue_wait_s(self):
        """Total queue residence the client paid: the small model's wait
        plus, for escalated frames, the big model's."""
        total = self._fut.queue_wait_s
        with self._lock:
            big = self._big_fut
        if big is not None and big.queue_wait_s is not None:
            total = (total or 0.0) + big.queue_wait_s
        return total

    def result(self, timeout=None):
        if self._mode == "direct":
            return self._fut.result(timeout)
        records = self._fut.result(timeout)
        req = None
        with self._lock:
            if not self._decided:
                self._decided = True
                h = self._fut.hardness
                req = self._fut.request
                if (h is not None and req is not None
                        and self._router.should_escalate(h)):
                    try:
                        self._big_fut = self._router._escalate(
                            req, deadline_ms=self._deadline_ms)
                        self._escalated = True
                    except Exception:
                        # big model refused (queue full, draining):
                        # degrade gracefully to the small answer instead
                        # of turning a served request into a 503
                        self._router._note_escalation_rejected()
                if not self._escalated:
                    self._router._note_answered_small()
            big = self._big_fut
        if big is None:
            return records
        out = big.result(timeout)
        with self._lock:
            first = not self._counted_big
            self._counted_big = True
            req = self._fut.request
        if first and req is not None:
            self._router._note_escalated_result(req, out)
        return out

    def provenance(self) -> dict:
        """The ``cascade`` response field: which model answered and why."""
        if self._mode == "direct":
            doc = {"model": self._model, "escalated": False}
            if self._reason:
                doc["reason"] = self._reason
            return doc
        with self._lock:
            esc = self._escalated
        doc = {"model": self._router.big if esc else self._router.small,
               "escalated": esc, "thresh": self._router.thresh}
        h = self._fut.hardness
        if h is not None:
            doc["hardness"] = round(float(h), 4)
        return doc


class CascadeRouter:
    """Accuracy-aware request router over a (small, big) model pair.

    Every gated request first hits the SMALL model; the on-device
    confidence gate — the registry program ``kind="cascade_gate"``,
    AOT-markered and warm-boot loadable exactly like the stream layer's
    ``frame_delta`` — folds the small model's still-on-device
    ``(B, cap, 6)`` detections into per-image hardness (the shared
    ``flywheel/hardness.py`` definition, so serving and mining can never
    drift) and stamps it on each request's future before readback: zero
    extra h2d transfers.  Frames whose hardness clears
    ``thresh * HARDNESS_MAX`` re-submit to the BIG model through
    :meth:`~mx_rcnn_tpu.serve.engine.ServeEngine.submit_staged` — the
    staged uint8 buffer is reused byte-for-byte, never re-staged — and
    ride the ordinary pool scheduler.  Escalated frames also feed the
    flywheel capture ring tagged ``cascade_escalated`` with the big
    model's records: serving traffic mines exactly the examples the
    small model needs.

    Routing per tenant (the addressed model id): the small/default
    entry gates; the big entry is served directly ("addressed"); an
    entry with ``fidelity="full"`` pins to the big model ("fidelity" —
    the per-SLO-class escape hatch); any other pool sibling bypasses
    the cascade untouched.
    """

    KIND = "cascade_gate"

    def __init__(self, pool: ModelPool, small: str, big: str,
                 thresh: float = 0.5):
        if small == big:
            raise ValueError("--cascade needs two DISTINCT models, got "
                             f"{small!r} twice")
        if not 0.0 <= float(thresh) <= 1.0:
            raise ValueError(f"cascade thresh must be in [0, 1], got "
                             f"{thresh}")
        from mx_rcnn_tpu.flywheel.hardness import (HARDNESS_MAX,
                                                   build_device_hardness)

        self.pool = pool
        self.small = small
        self.big = big
        self.thresh = float(thresh)
        self._thresh_raw = self.thresh * HARDNESS_MAX
        self.small_entry = pool.entry(small)   # KeyError = unknown model
        self.big_entry = pool.entry(big)
        se, be = self.small_entry.engine, self.big_entry.engine
        for eng, mid in ((se, small), (be, big)):
            if not eng.opts.serve_e2e:
                raise ValueError(
                    f"--cascade requires --serve-e2e on every cascade "
                    f"model (the gate consumes the fused program's "
                    f"on-device detections); model {mid!r} is not e2e")
        # escalation reuses the small model's staged buffers, so both
        # engines must agree on bucket geometry for every orientation
        for h, w in ((100, 200), (200, 100)):
            if se.bucket_key(h, w) != be.bucket_key(h, w):
                raise ValueError(
                    f"cascade models disagree on bucket geometry "
                    f"({small}: {se.bucket_key(h, w)} vs {big}: "
                    f"{be.bucket_key(h, w)} for a {h}x{w} image) — "
                    f"escalation cannot reuse staged pixels; align "
                    f"SCALES and strides")
        self._lock = threading.Lock()
        self.counters = {"answered_small": 0, "escalated": 0,
                         "forced_big": 0, "gate_batches": 0,
                         "escalation_rejected": 0}
        self.hists = {"cascade/gate_time": Hist(),
                      "cascade/hardness": Hist()}
        # registry citizenship: the gate program registers on the SMALL
        # model's registry (it consumes that model's detections), giving
        # it AOT markers + warm-boot accounting like any other program
        self._registry = getattr(se, "registry", None)
        if self._registry is not None:
            self._registry.register(self.KIND,
                                    lambda: build_device_hardness())
            self._fn = self._registry.lookup(self.KIND)
        else:
            self._fn = build_device_hardness()
        # escalated frames feed the pool's capture ring (the sink hangs
        # off the default/small engine; NULL sink when capture is off)
        self.capture = se.capture
        se.cascade = self

    # -- the on-device gate ---------------------------------------------

    def _dispatch_gate(self, dets, dvalid):
        """Run the gate program on the still-on-device detection tensors;
        returns (hardness ndarray, wall seconds).  First-dispatch
        accounting goes through the registry like every other program."""
        import numpy as np

        reg = self._registry
        shape = tuple(dets.shape)
        first = reg.note_dispatch(self.KIND, shape) \
            if reg is not None else False
        t0 = time.perf_counter()
        hard = np.asarray(self._fn(dets, dvalid))  # (B,) readback
        dt = time.perf_counter() - t0
        if first and reg is not None:
            reg.record_compile_seconds(self.KIND, shape, dt)
        return hard, dt

    def gate_batch(self, dets, dvalid, reqs) -> None:
        """Engine hook (small model's ``_finish_e2e``): stamp per-image
        hardness + a request backlink on each future, observe gate cost,
        and emit the PR-16 trace span carrying the gate verdict."""
        hard, dt = self._dispatch_gate(dets, dvalid)
        tel = telemetry.get()
        self.hists["cascade/gate_time"].observe(dt)
        tel.observe("cascade/gate_time", dt)
        with self._lock:
            self.counters["gate_batches"] += 1
        tel.counter("cascade/gate_batches")
        tracer = tracectx.get()
        for r in reqs:
            h = float(hard[r.row])  # its row of the staging batch
            r.future.hardness = h
            r.future.request = r
            self.hists["cascade/hardness"].observe(h)
            ctx = r.trace
            if tracer.enabled and ctx is not None and ctx.sampled:
                tracer.record(ctx, "cascade/gate", dt,
                              attrs={"hardness": round(h, 4),
                                     "escalate": bool(
                                         self.should_escalate(h)),
                                     "thresh": self.thresh,
                                     "small": self.small,
                                     "big": self.big})

    def should_escalate(self, hardness: float) -> bool:
        """thresh 0 escalates everything (>= comparison), 1 nothing
        (the bound is unreachable) — the threshold-sweep contract."""
        return hardness >= self._thresh_raw

    def warmup(self) -> int:
        """Compile the gate program before traffic (and before
        ``mark_ready``): one dispatch on a zeros detection tensor of the
        steady-state shape — identical for both orientation buckets, so
        one program covers them.  Returns new registry programs (0 on a
        warm boot where only the AOT marker is re-probed... the program
        still counts once per process; callers compare aot_hit)."""
        import jax
        import numpy as np

        eng = self.small_entry.engine
        B = eng.opts.batch_size
        mpi = int(self.small_entry.cfg.TEST.MAX_PER_IMAGE)
        before = self._registry.counters["programs"] \
            if self._registry is not None else 0
        dets = jax.device_put(np.zeros((B, mpi, 6), np.float32))
        dvalid = jax.device_put(np.zeros((B, mpi), bool))
        self._dispatch_gate(dets, dvalid)
        after = self._registry.counters["programs"] \
            if self._registry is not None else before
        return after - before

    # -- routing ---------------------------------------------------------

    def submit(self, image, deadline_ms=None, stream=None, trace=None,
               model_id=None) -> CascadeFuture:
        """Route one request.  Raises ``KeyError`` for an unknown model
        id (the frontend's 404) and the engine's admission errors."""
        entry = self.pool.entry(model_id)
        mid = entry.model_id
        tel = telemetry.get()
        if mid == self.big:
            fut = entry.engine.submit(image, deadline_ms=deadline_ms,
                                      stream=stream, trace=trace)
            return CascadeFuture(self, fut, "direct", mid,
                                 reason="addressed")
        if entry.fidelity == "full":
            with self._lock:
                self.counters["forced_big"] += 1
            tel.counter("cascade/forced_big")
            fut = self.big_entry.engine.submit(
                image, deadline_ms=deadline_ms, stream=stream, trace=trace)
            return CascadeFuture(self, fut, "direct", self.big,
                                 reason="fidelity")
        if mid != self.small:
            # a pool sibling outside the cascade pair: untouched
            fut = entry.engine.submit(image, deadline_ms=deadline_ms,
                                      stream=stream, trace=trace)
            return CascadeFuture(self, fut, "direct", mid, reason="bypass")
        fut = entry.engine.submit(image, deadline_ms=deadline_ms,
                                  stream=stream, trace=trace)
        return CascadeFuture(self, fut, "gate", mid,
                             deadline_ms=deadline_ms)

    # -- decision bookkeeping (called by CascadeFuture, once each) -------

    def _escalate(self, req, deadline_ms=None):
        fut = self.big_entry.engine.submit_staged(
            req.image, req.raw_hw, req.ratio, req.im_info, req.orig_hw,
            deadline_ms=deadline_ms, stream=req.stream, trace=req.trace)
        tel = telemetry.get()
        with self._lock:
            self.counters["escalated"] += 1
            rate = self._rate_locked()
        tel.counter("cascade/escalated")
        tel.gauge("cascade/escalation_rate", rate)
        return fut

    def _note_answered_small(self):
        tel = telemetry.get()
        with self._lock:
            self.counters["answered_small"] += 1
            rate = self._rate_locked()
        tel.counter("cascade/answered_small")
        tel.gauge("cascade/escalation_rate", rate)

    def _note_escalation_rejected(self):
        with self._lock:
            self.counters["escalation_rejected"] += 1
        telemetry.get().counter("cascade/escalation_rejected")

    def _note_escalated_result(self, req, records):
        """Big model answered an escalated frame: feed the capture ring,
        tagged, with the BIG model's records as the pseudo-labels — the
        small model's miss becomes its next training example."""
        cap = self.capture
        if cap is None or not cap.enabled:
            return
        trace_id = req.trace.trace_id if req.trace is not None else None
        cap.record_batch(
            [(req.image, req.raw_hw, req.orig_hw, records, trace_id,
              {"tags": ["cascade_escalated"]})],
            self.big_entry.engine.generation)

    def _rate_locked(self) -> float:
        dec = self.counters["answered_small"] + self.counters["escalated"]
        return self.counters["escalated"] / max(1, dec)

    def escalation_rate(self) -> float:
        with self._lock:
            return self._rate_locked()

    # -- introspection ---------------------------------------------------

    def metrics(self) -> dict:
        with self._lock:
            counters = dict(self.counters)
            rate = self._rate_locked()
        out = {"small": self.small, "big": self.big,
               "thresh": self.thresh,
               "counters": counters,
               "escalation_rate": round(rate, 4)}
        stats = {}
        for q, tag in ((0.5, "p50"), (0.99, "p99")):
            v = self.hists["cascade/gate_time"].quantile(q)
            if v is not None:
                stats[f"gate_time_{tag}_ms"] = round(v * 1e3, 3)
            h = self.hists["cascade/hardness"].quantile(q)
            if h is not None:
                stats[f"hardness_{tag}"] = round(h, 4)
        out["latency"] = stats
        return out
