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

import resource
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
           "shutdown", "stage", "thread_usage"]

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


def thread_usage():
    """``(cpu_s, minflt)`` of the calling thread since it started, from ONE
    ``getrusage(RUSAGE_THREAD)``: its user + system seconds and its minor
    page faults.  The difference of two readings on one thread is what it
    ran, and faulted, in between; wall time less that CPU is time it did
    not run: waiting for the GIL, a lock, a core or the device.  The
    seconds advance at the scheduler's tick (4 ms on a Linux of HZ=250, 10
    ms on the chip's host, whose thread CPU clock is no finer), so one use
    of a short stage reads 0 or a tick; a window's sum over many uses is
    what a reader takes.  One syscall, not two (the thread CPU clock beside
    it): on the chip's host each costs about 6 µs, under the GIL."""
    r = resource.getrusage(resource.RUSAGE_THREAD)
    return r.ru_utime + r.ru_stime, r.ru_minflt


class stage:
    """One timed stage: ``with telemetry.stage("serve/forward", hist) as st``.

    Every use (the object may be entered again: one per batch, entered per
    image) opens a ``jax.profiler.TraceAnnotation(name)`` — so the span lies
    on the profiler's own clock beside the device's ops, at a few hundred ns
    when no profile is being taken — takes ``perf_counter()`` at entry and
    exit, and adds the duration to ``seconds`` (the total over ``uses``),
    for callers that hand the time on (tracectx phases, compile seconds).
    Inside those two instants it reads :func:`thread_usage` once each, and
    adds the thread's CPU seconds and minor page faults in between to
    ``cpu_seconds`` and ``minflt``: a use is entered and left on ONE thread
    (no stage of the program crosses threads; ``tests/test_stage_cpu.py``
    holds the server to it), so ``seconds - cpu_seconds`` is what the
    thread spent not running — over many uses: one use's CPU is good to a
    scheduler's tick.

    With a ``hist`` each use is booked as it ends: one observation into the
    hist (with its CPU and faults) and, when a sink is on, one span into it
    (``add``, what ``tel.span`` did — in trace mode with the wall-clock
    start).  Without one nothing is booked: the owner calls :meth:`book`
    where the hist is known only later or the stage is summed over a
    batch's images, or leaves it at the annotation and the clock.

    jax is never imported here: the annotation is entered only where the
    process already has it (``"jax" in sys.modules``).  ``annotate=False``
    keeps the clocks and leaves the timeline alone — for a stage whose
    parts are annotated themselves: an enclosing event would cover every
    idle gap and name none of them."""

    __slots__ = ("name", "hist", "annotate", "seconds", "cpu_seconds",
                 "minflt", "uses", "_ann", "_t0", "_w0", "_u0")

    def __init__(self, name: str, hist: Optional[Hist] = None,
                 annotate: bool = True):
        self.name = name
        self.hist = hist
        self.annotate = annotate
        self.seconds = 0.0
        self.cpu_seconds = 0.0
        self.minflt = 0
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
        self._u0 = thread_usage()   # inside the wall clock's two instants
        return self

    def __exit__(self, *exc):
        cpu, flt = thread_usage()
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        cpu -= self._u0[0]
        flt -= self._u0[1]
        self.seconds += dt
        self.cpu_seconds += cpu
        self.minflt += flt
        self.uses += 1
        if self.hist is not None:
            self.hist.observe(dt, cpu_s=cpu, minflt=flt)
            if _active.enabled:
                _active.add(self.name, dt, ts=self._w0)
        return False

    def book(self, hist: Hist) -> None:
        """All uses so far as ONE observation into ``hist`` (with their
        summed CPU and faults) and one span record (``n`` = the uses) into
        the sink when it is on."""
        hist.observe(self.seconds, cpu_s=self.cpu_seconds,
                     minflt=self.minflt)
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
