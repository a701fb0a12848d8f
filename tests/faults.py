"""Deterministic fault-injection harness for the resilience subsystem
(ISSUE 2 tentpole).  NOT a test module — pytest ignores it (no ``test_``
prefix); tests/test_resilience.py drives every injector, and the
env-driven CLI-level injectors live in ``mx_rcnn_tpu/train/resilience.py``
(``MXR_FAULT_*``) for script/fault_smoke.sh.

Injectors:

* :func:`corrupt_record` — make one roidb record unloadable (exercises the
  loader's bad-record isolation).
* :class:`NanBatchLoader` — poison the images of one global batch with NaN
  (exercises the train-step sentinel + nan policies).
* :class:`SignalAtBatchLoader` — raise SIGTERM/SIGINT in the consumer
  thread while a chosen batch is being pulled (exercises graceful
  preemption at an exact, reproducible step boundary).
* :func:`flaky_saves` — fail the first N orbax saves with OSError
  (exercises checkpoint I/O retry).
* :func:`hang_until` — a producer generator that yields its items then
  blocks until released (exercises the prefetch-queue watchdog).

Serve-side chaos (ISSUE 8): the injectors themselves live in
``mx_rcnn_tpu/serve/replica.py`` (``MXR_FAULT_REPLICA_*``, parsed by
``ReplicaFaults`` — package code, same placement rule as the
``MXR_FAULT_*`` train injectors above); this module only provides
:func:`replica_fault_env`, the composer tests use to build the env dict
for a chosen replica index, so the var names have exactly one spelling.

Fabric-side network chaos (ISSUE 12) follows the same split:
``MXR_FAULT_NET_{DROP,DELAY_MS,RESET}`` are parsed by ``NetFaults`` in
``mx_rcnn_tpu/serve/replica.py`` and injected member-side at the HTTP
frontend; :func:`net_fault_env` is the composer for
tests/test_fabric.py.

Flywheel capture chaos (ISSUE 13), same split again:
``MXR_FAULT_FLYWHEEL_{CORRUPT_SHARD,TRUNCATE_SPILL}`` (value = the
0-based index of the spilled shard to damage) are parsed by
``RequestCapture`` in ``mx_rcnn_tpu/flywheel/capture.py``;
:func:`flywheel_fault_env` is the composer for tests/test_flywheel.py.
The damaged shard's replay records then
exercise the loader's PR-2 bad-record substitution path.

Fleet-flywheel chaos (ISSUE 17), same split: the fleet fault env vars
are parsed by package code (``MXR_FAULT_FLYWHEEL_DUP_MANIFEST`` in
``flywheel/capture.py``; ``MXR_FAULT_FLYWHEEL_{PARTITION_MINE,
KILL_TRAIN}`` in ``flywheel/fleet.py``); :func:`fleet_fault_env` is
the composer for tests/test_flywheel_fleet.py."""

from __future__ import annotations

import contextlib
import signal
import time

import numpy as np


def corrupt_record(roidb: list, i: int) -> list:
    """Make ``roidb[i]`` unloadable: drop inline pixels, point the image
    path at nothing — ``_load_record`` raises on it.  ``AnchorLoader``
    copies the list it is given, so a fault meant for a built loader goes
    into ``loader.roidb``, not into the list it was built from."""
    rec = dict(roidb[i])
    rec.pop("image_array", None)
    rec["image"] = "/nonexistent/faults_harness_corrupt.jpg"
    roidb[i] = rec
    return roidb


class NanBatchLoader:
    """Wrap a train loader; the ``n``-th yielded batch (counted globally
    across epochs) gets all-NaN images."""

    def __init__(self, inner, n: int):
        self._inner = inner
        self._n = n
        self._count = 0
        self.batch_size = inner.batch_size

    @property
    def steps_per_epoch(self) -> int:
        return self._inner.steps_per_epoch

    def __iter__(self):
        for b in self._inner:
            if self._count == self._n:
                b = dict(b)
                b["images"] = np.full_like(b["images"], np.nan)
            self._count += 1
            yield b


class SignalAtBatchLoader:
    """Wrap a train loader; raise ``sig`` on the consumer thread right
    before yielding batch ``at`` (global count) — the trainer's handler
    sets its flag, batch ``at`` still dispatches, and the preemption save
    lands at the following boundary (``consumed = at + 1``), every run."""

    def __init__(self, inner, at: int, sig=signal.SIGTERM):
        self._inner = inner
        self._at = at
        self._sig = sig
        self._count = 0
        self.batch_size = inner.batch_size

    @property
    def steps_per_epoch(self) -> int:
        return self._inner.steps_per_epoch

    def __iter__(self):
        for b in self._inner:
            if self._count == self._at:
                signal.raise_signal(self._sig)
            self._count += 1
            yield b


@contextlib.contextmanager
def flaky_saves(n: int, exc=OSError):
    """Patch ``orbax.checkpoint.CheckpointManager.save`` to raise ``exc``
    for the first ``n`` calls, then behave normally — the transient-
    filesystem-error shape ``resilience.retry_io`` exists for.  Yields the
    mutable ``{"left": remaining}`` counter."""
    import orbax.checkpoint as ocp

    orig = ocp.CheckpointManager.save
    calls = {"left": n}

    def save(self, *a, **k):
        if calls["left"] > 0:
            calls["left"] -= 1
            raise exc("injected transient save failure (tests/faults.py)")
        return orig(self, *a, **k)

    ocp.CheckpointManager.save = save
    try:
        yield calls
    finally:
        ocp.CheckpointManager.save = orig


def replica_fault_env(index: int, kill_after=None, hang_after=None,
                      slow_start_s=None, corrupt_ckpt=False) -> dict:
    """Compose the ``MXR_FAULT_REPLICA_*`` env dict injecting the chosen
    faults into replica ``index`` (merge into the child's env, or the
    parent's — tokens are index-matched, so siblings are untouched)."""
    from mx_rcnn_tpu.serve.replica import (ENV_CORRUPT_CKPT,
                                           ENV_HANG_AFTER, ENV_KILL_AFTER,
                                           ENV_SLOW_START)

    env = {}
    if kill_after is not None:
        env[ENV_KILL_AFTER] = f"{index}:{int(kill_after)}"
    if hang_after is not None:
        env[ENV_HANG_AFTER] = f"{index}:{int(hang_after)}"
    if slow_start_s is not None:
        env[ENV_SLOW_START] = f"{index}:{float(slow_start_s)}"
    if corrupt_ckpt:
        env[ENV_CORRUPT_CKPT] = str(index)
    return env


def net_fault_env(index: int, drop_after=None, delay_ms=None,
                  reset_from=None, reset_to=None) -> dict:
    """Compose the ``MXR_FAULT_NET_*`` env dict injecting network faults
    into fabric member ``index`` (index-matched tokens, like
    :func:`replica_fault_env`):

    * ``drop_after=N`` — after serving N ``/predict`` requests the member
      blackholes EVERY path including probes (accepted connections hang):
      the network-partition shape, seen by the router as probe timeouts.
    * ``delay_ms=D`` — every ``/predict`` response is delayed by D ms
      (probes unaffected): the tail-latency shape request hedging exists
      for.
    * ``reset_from=N`` (optionally with ``reset_to=M``) — ``/predict``
      requests N..M (1-based, inclusive; open-ended without ``reset_to``)
      are answered with a hard TCP RST while probes stay healthy: the
      flaky-member shape that must trip the per-member circuit breaker
      (and, when bounded, let it close again after recovery)."""
    from mx_rcnn_tpu.serve.replica import (ENV_NET_DELAY, ENV_NET_DROP,
                                           ENV_NET_RESET)

    env = {}
    if drop_after is not None:
        env[ENV_NET_DROP] = f"{index}:{int(drop_after)}"
    if delay_ms is not None:
        env[ENV_NET_DELAY] = f"{index}:{float(delay_ms)}"
    if reset_from is not None:
        spec = (f"{int(reset_from)}" if reset_to is None
                else f"{int(reset_from)}-{int(reset_to)}")
        env[ENV_NET_RESET] = f"{index}:{spec}"
    return env


def flywheel_fault_env(corrupt_shard=None, truncate_spill=None) -> dict:
    """Compose the ``MXR_FAULT_FLYWHEEL_*`` env dict damaging a capture
    shard after its atomic spill (simulated torn disk):

    * ``corrupt_shard=N`` — shard index N's npz is overwritten with
      garbage bytes (np.load raises on every record).
    * ``truncate_spill=N`` — shard index N's npz is truncated to half
      its size (the torn-write shape)."""
    from mx_rcnn_tpu.flywheel.capture import (ENV_CORRUPT_SHARD,
                                              ENV_TRUNCATE_SPILL)

    env = {}
    if corrupt_shard is not None:
        env[ENV_CORRUPT_SHARD] = str(int(corrupt_shard))
    if truncate_spill is not None:
        env[ENV_TRUNCATE_SPILL] = str(int(truncate_spill))
    return env


def fleet_fault_env(partition_mine=None, dup_manifest=None,
                    kill_train=None) -> dict:
    """Compose the fleet-flywheel ``MXR_FAULT_FLYWHEEL_*`` env dict:

    * ``partition_mine="m1"`` (str or list of member ids) — those
      members are unreachable during the distributed mine; the fold
      proceeds without their rankings.
    * ``dup_manifest="m0"`` (member id, or ``"*"`` for every member) —
      each manifest write is delivered TWICE under distinct filenames
      (the at-least-once delivery shape the merge must fold to one
      member entry, highest seq winning).
    * ``kill_train=(round, seconds)`` — the trainer subprocess of the
      chosen round is SIGKILLed that many seconds in (mid-epoch)."""
    from mx_rcnn_tpu.flywheel.capture import ENV_DUP_MANIFEST
    from mx_rcnn_tpu.flywheel.fleet import (ENV_KILL_TRAIN,
                                            ENV_PARTITION_MINE)

    env = {}
    if partition_mine is not None:
        if isinstance(partition_mine, str):
            partition_mine = [partition_mine]
        env[ENV_PARTITION_MINE] = ",".join(partition_mine)
    if dup_manifest is not None:
        env[ENV_DUP_MANIFEST] = str(dup_manifest)
    if kill_train is not None:
        rnd, secs = kill_train
        env[ENV_KILL_TRAIN] = f"{int(rnd)}:{float(secs)}"
    return env


def hang_until(event, items):
    """Producer generator: yield ``items``, then spin until ``event`` is
    set — a stuck-but-alive producer (hung filesystem read) for the
    prefetch watchdog.  Set ``event`` in the test's cleanup so the
    producer thread exits promptly."""
    for it in items:
        yield it
    while not event.is_set():
        time.sleep(0.02)
