"""Telemetry layer — structured run-time instrumentation for the whole
stack (SURVEY §5's "free win" the MXNet reference never had).

Dependency-free (stdlib only — no jax import, so the data layer's
producer threads and host-only tools can emit without touching the
backend).  One module-global active sink, because the instrumented code
is cross-cutting: the trainer, the loader's prefetch thread, the
Speedometer and the eval loop all record into whatever run is active
without threading a handle through every constructor.

    from mx_rcnn_tpu import telemetry

    telemetry.configure(out_dir, rank=jax.process_index(),
                        world=jax.process_count())
    with telemetry.get().span("train/dispatch"):
        ...
    telemetry.get().counter("train/recompile")
    telemetry.shutdown()   # close the event file, restore the no-op sink

Unconfigured, ``get()`` returns the shared :data:`NULL` no-op sink —
instrumented hot paths pay one attribute check and zero allocations.
Drivers expose this as ``--telemetry-dir`` (per-rank event files on
multi-host, summary JSON from process 0 only — the ``profile_dir``
rank-split contract); ``scripts/telemetry_report.py`` folds the files
back into the human table.
"""

from __future__ import annotations

import sys
import time
from typing import Optional

from mx_rcnn_tpu.telemetry.sink import (HIST_LE, NULL, RING_SIZE,
                                        SCHEMA_VERSION, SUMMARY_NAME, Hist,
                                        NullTelemetry, Telemetry,
                                        quantile_from_counts)

__all__ = ["Telemetry", "NullTelemetry", "NULL", "RING_SIZE",
           "SCHEMA_VERSION", "SUMMARY_NAME", "Hist", "HIST_LE",
           "quantile_from_counts", "configure", "get", "reset_null",
           "shutdown", "stage"]

_active: "NullTelemetry | Telemetry" = NULL


def configure(out_dir: str, rank: int = 0, world: int = 1,
              run_meta: Optional[dict] = None, stream: bool = True,
              trace: Optional[bool] = None) -> Telemetry:
    """Open a run's sink and make it the active one.  Reconfiguring over a
    live sink closes it first (one active run per process — matching the
    one-event-file-per-rank layout).  ``stream=False`` keeps the sink
    purely in-memory (aggregates + flight ring, no event file) — the obs
    server uses it when ``--obs-port`` is set without ``--telemetry-dir``.
    ``trace`` opts span records into wall-start timestamps (default: the
    ``MXR_TELEMETRY_TRACE`` env var)."""
    global _active
    if _active.enabled:
        _active.close()
    _active = Telemetry(out_dir, rank=rank, world=world, run_meta=run_meta,
                        stream=stream, trace=trace)
    return _active


def get() -> "NullTelemetry | Telemetry":
    """The active sink (the no-op :data:`NULL` when none is configured)."""
    return _active


class stage:
    """One timed stage: ``with telemetry.stage("serve/forward", hist) as st``.

    Every use (the object may be entered again: one per batch, entered per
    image) opens a ``jax.profiler.TraceAnnotation(name)`` — so the span lies
    on the profiler's own clock beside the device's ops, at a few hundred ns
    when no profile is being taken — takes ``perf_counter()`` at entry and
    exit, and adds the duration to ``seconds`` (the total over ``uses``),
    for callers that hand the time on (tracectx phases, compile seconds).

    With a ``hist`` each use is booked as it ends: one observation into the
    hist and, when a sink is on, one span into it (``add``, what
    ``tel.span`` did — in trace mode with the wall-clock start).  Without
    one nothing is booked: the owner calls :meth:`book` where the hist is
    known only later or the stage is summed over a batch's images, or
    leaves it at the annotation and the clock.

    jax is never imported here: the annotation is entered only where the
    process already has it (``"jax" in sys.modules``).  ``annotate=False``
    keeps the clocks and leaves the timeline alone — for a stage whose
    parts are annotated themselves: an enclosing event would cover every
    idle gap and name none of them."""

    __slots__ = ("name", "hist", "annotate", "seconds", "uses", "_ann",
                 "_t0", "_w0")

    def __init__(self, name: str, hist: Optional[Hist] = None,
                 annotate: bool = True):
        self.name = name
        self.hist = hist
        self.annotate = annotate
        self.seconds = 0.0
        self.uses = 0

    def __enter__(self) -> "stage":
        jax = sys.modules.get("jax") if self.annotate else None
        profiler = getattr(jax, "profiler", None)
        self._ann = (profiler.TraceAnnotation(self.name)
                     if profiler is not None else None)
        if self._ann is not None:
            self._ann.__enter__()
        self._w0 = time.time() if _active.trace else None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.seconds += dt
        self.uses += 1
        if self.hist is not None:
            self.hist.observe(dt)
            if _active.enabled:
                _active.add(self.name, dt, ts=self._w0)
        return False

    def book(self, hist: Hist) -> None:
        """All uses so far as ONE observation into ``hist`` and one span
        record (``n`` = the uses) into the sink when it is on."""
        hist.observe(self.seconds)
        if _active.enabled:
            _active.add(self.name, self.seconds, n=self.uses)


def reset_null():
    """Drop the active sink WITHOUT closing it — for forked children
    (loader workers) that inherit the parent's open event stream.  The
    child must stop emitting (its writes would interleave with the
    parent's JSONL through the shared fd) but must not flush/close a file
    the parent still owns."""
    global _active
    _active = NULL


def shutdown():
    """Close the active sink and restore the no-op default."""
    global _active
    _active.close()
    _active = NULL
