"""REAL multi-process (multi-host) training — the `dist_sync` tier.

Spawns two OS processes, each owning 4 virtual CPU devices, joined into
one 8-device global mesh by ``jax.distributed`` (Gloo collectives), and
runs three full ``fit`` phases — AnchorLoader with the ``num_parts`` row
partition, global-array batch assembly (``global_from_local``, flat AND
stacked), XLA cross-process gradient all-reduce, orbax save AND restore
with every rank participating — then checks against a single-process
8-device control run on the SAME global data and seeds:

* the two ranks end bit-identical after EVERY phase (replicated state
  really is replicated across processes — including through a
  checkpoint restore);
* multi-process final params match the single-process control per phase
  (allclose: cross-process Gloo all-reduce may round differently than
  the single-process reduction).

Phases (see mp_worker.py): 1 = fit+save, 2 = resume (orbax multi-host
restore barriers), 3 = steps_per_dispatch=2 (stacked global assembly on
the producer thread).

This is the strongest multi-host evidence the environment can produce
without a second TPU host; on a pod the same code path is
``train_end2end.py --dist-auto`` (reference: SURVEY §2.2 KVStore
``dist_sync`` row — upstream left it unscripted).
"""

from __future__ import annotations

import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np

WORKER = os.path.join(os.path.dirname(__file__), "mp_worker.py")
# One limit for the whole test — the two ranks, then the control — well
# under the suite's own (1,470 s for every file together): past it the
# test fails alone, where a longer wait would have the suite cut and
# every later test lost.  Readings on this 8-core box (PR 30): 166 s with
# the per-rank compile caches warm, 961 s with them cold beside six busy
# cores (each rank compiles the train step, the resumed one and the k=2
# scanned one), so a cold run under the suite's six workers may fail once
# and leaves the caches warm for the next.
DEADLINE_S = 900

PHASES = ("PHASE1", "PHASE2", "PHASE3")


def _run(pid: int, nproc: int, port: int, ckpt_dir: str) -> subprocess.Popen:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    repo = os.path.dirname(os.path.dirname(__file__))
    prior = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = repo + (os.pathsep + prior if prior else "")
    return subprocess.Popen(
        [sys.executable, WORKER, str(pid), str(nproc), str(port), ckpt_dir],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env)


def _parse(out: str, phase: str):
    digest = float(re.search(rf"{phase} DIGEST (\S+)", out).group(1))
    probe = np.asarray(
        [float(v)
         for v in re.search(rf"{phase} PROBE (.+)", out).group(1).split()])
    step = int(re.search(rf"{phase} STEP (\d+)", out).group(1))
    return digest, probe, step


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _run_all(tmp_path):
    """-> (the two ranks' outputs, the control's), all three exited 0
    inside the one deadline."""
    t_end = time.monotonic() + DEADLINE_S

    def left():
        return max(t_end - time.monotonic(), 1)

    port = _free_port()
    mp_ckpt = str(tmp_path / "mp2")  # ranks SHARE this prefix (orbax
    # writes from the primary host, barriers on both)
    workers = [_run(i, 2, port, mp_ckpt) for i in range(2)]
    outs = []
    try:
        for i, p in enumerate(workers):
            out, _ = p.communicate(timeout=left())
            outs.append(out.decode())
        for i, p in enumerate(workers):
            assert p.returncode == 0, f"rank {i} failed:\n{outs[i][-4000:]}"
    finally:
        for p in workers:  # a crashed rank must not orphan its peer
            if p.poll() is None:
                p.kill()

    control_p = _run(0, 1, port, str(tmp_path / "ctl"))
    try:
        out, _ = control_p.communicate(timeout=left())
    finally:
        if control_p.poll() is None:
            control_p.kill()
    control_out = out.decode()
    assert control_p.returncode == 0, control_out[-4000:]
    return outs, control_out


def test_two_process_fit_matches_single_process(tmp_path):
    try:
        outs, control_out = _run_all(tmp_path)
    finally:
        # 811 MB of checkpoints a run, and pytest keeps the last three
        # runs' directories: the driver's run of PR 29 failed here on
        # "No space left on device" (OS error 28) while the control saved
        shutil.rmtree(tmp_path / "mp2", ignore_errors=True)
        shutil.rmtree(tmp_path / "ctl", ignore_errors=True)

    # 16 imgs / global batch 8 = 2 steps per epoch in every phase
    want_step = {"PHASE1": 2, "PHASE2": 4, "PHASE3": 2}
    for phase in PHASES:
        d0, p0, s0 = _parse(outs[0], phase)
        d1, p1, s1 = _parse(outs[1], phase)
        dc, pc, sc = _parse(control_out, phase)

        # ranks are bit-identical (the state is one replicated global
        # array) — through save, restore and stacked dispatch alike
        assert d0 == d1 and np.array_equal(p0, p1), (phase, d0, d1, p0, p1)
        assert s0 == s1 == sc == want_step[phase], (phase, s0, s1, sc)
        if phase == "PHASE2":
            # resume starts from each run's OWN phase-1 checkpoint, and
            # multi vs control phase-1 params already differ by reduction-
            # order rounding (~1e-7) — which the detector's discrete
            # top-k/NMS can amplify chaotically over the resumed epoch, so
            # a tight control comparison would be flaky by construction.
            # The restore evidence is the bit-identity + step assertions
            # above (both ranks restored the same bytes and advanced in
            # lockstep) plus a finite digest.
            assert np.isfinite(d0), (phase, d0)
            continue
        # multi-process == single-process control up to reduction order
        np.testing.assert_allclose(p0, pc, rtol=1e-5, atol=1e-7,
                                   err_msg=phase)
        assert abs(d0 - dc) / max(abs(dc), 1.0) < 1e-5, (phase, d0, dc)
