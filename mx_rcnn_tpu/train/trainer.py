"""``fit`` — the reference's ``Module.fit`` call in ``train_net``
(train_end2end.py), as an explicit loop over the jitted step.

Responsibilities mirrored: per-epoch data iteration, composite metrics,
Speedometer batch-end callback, do_checkpoint epoch-end callback, resume
(the reference's ``--resume`` loads the begin_epoch checkpoint and
continues).  Batches are transferred (and mesh-scattered — the Module ctx
split) from the loader's prefetch thread via its ``put`` hook, so the
host→device copy overlaps the previous step's compute; loaders without
the hook fall back to a synchronous per-step ``shard_batch``.  Dispatch is
async — metrics are fetched one step late so the host never blocks the
device on the current step's scalars.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax
import numpy as np

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.parallel.mesh import MeshPlan, shard_batch, shard_stacked_batch
from mx_rcnn_tpu.train.callback import Speedometer
from mx_rcnn_tpu.train.checkpoint import CheckpointManager
from mx_rcnn_tpu.train.metric import MetricBank
from mx_rcnn_tpu.train.resilience import (NonFiniteLossError,
                                          PreemptionGuard, ResilienceOptions,
                                          dump_nan_diagnostics,
                                          nan_injection_step,
                                          preemption_agreed)
from mx_rcnn_tpu.train.train_step import (TrainState, create_train_state,
                                          make_multi_train_step,
                                          make_train_step)


def _runtime_owned(tree):
    """Deep-copy restored host (numpy) leaves into runtime-owned device
    buffers before they reach the donated step function.

    Orbax restores into numpy arrays.  On the CPU backend jax converts a
    numpy argument zero-copy — the device buffer aliases memory that numpy
    still owns — and ``donate_argnums`` then lets XLA reuse that aliased
    input buffer for the step's OUTPUT params.  The moment the restored
    tree is dropped (the old ``TrainState`` dies at rebind), numpy frees
    the memory under the live output, which then reads back as heap
    garbage.  An explicit device copy breaks the alias; every restore path
    that feeds ``TrainState`` must go through this."""
    return jax.tree.map(
        lambda a: jax.numpy.array(a) if isinstance(a, np.ndarray) else a,
        tree)


# Tuned steady state must hide the input pipeline: the fraction of epoch
# wall spent blocked on the loader beyond this trips a telemetry counter
# + flight-recorder-visible meta event (train/pipeline.py sweeps use the
# same threshold to mark a cell as loader-bound).
LOADER_WAIT_TRIPWIRE_FRAC = 0.10


def _make_group_wrap(k: int, plan: Optional[MeshPlan], prep=None):
    """Producer-thread group assembly for ``steps_per_dispatch=k``.

    Returns a generator transform (the loader ``wrap`` hook): stacks k
    consecutive shape-homogeneous host batches and ships the group
    (``shard_stacked_batch``) FROM THE PREFETCH THREAD, so k>1 keeps the
    same transfer/compute overlap the k=1 ``put`` hook provides.  A scale/
    orientation bucket change flushes the partial group as single sharded
    batches (groups must be shape-homogeneous — one compiled program per
    bucket), as does the epoch remainder.  Items arrive at the consumer
    tagged ``(kind, n_batches, on_device_data)``.
    """
    if prep is not None:  # device-side preprocessing (plan is None here)
        put1, putk = prep.put, prep.put_stacked
    else:
        put1 = ((lambda b: shard_batch(plan, b)) if plan is not None
                else jax.device_put)
        putk = ((lambda s: shard_stacked_batch(plan, s)) if plan is not None
                else jax.device_put)

    def wrap(gen):
        buf = []

        def flush():
            for b in buf:
                yield ("single", 1, put1(b))
            buf.clear()

        for batch in gen:
            if buf and buf[0]["images"].shape != batch["images"].shape:
                yield from flush()
            buf.append(batch)
            if len(buf) == k:
                stacked = jax.tree.map(lambda *xs: np.stack(xs), *buf)
                buf.clear()
                yield ("group", k, putk(stacked))
        yield from flush()

    return wrap


def _reset_schedule_counts(opt_state, value: int = 0):
    """Set every ``count`` leaf in an optax state tree to ``value`` — the
    number of optimizer updates already taken against the CURRENT schedule
    basis: 0 for an epoch-boundary resume (the schedule is rebuilt relative
    to ``begin_epoch``), ``consumed`` for a mid-epoch step resume (rebuilt
    relative to that epoch, with ``consumed`` steps already inside it)."""

    def reset(path, leaf):
        names = [getattr(e, "name", getattr(e, "key", "")) for e in path]
        if names and names[-1] == "count":
            return jax.numpy.full_like(leaf, value)
        return leaf

    return jax.tree_util.tree_map_with_path(reset, opt_state)


def fit(cfg: Config, model, params, train_loader,
        begin_epoch: int = 0, end_epoch: int = 10,
        plan: Optional[MeshPlan] = None,
        prefix: Optional[str] = None,
        graph: str = "end2end",
        seed: int = 0,
        frequent: int = 20,
        resume: bool = False,
        profile_dir: Optional[str] = None,
        telemetry_dir: Optional[str] = None,
        steps_per_dispatch: int = 1,
        fixed_prefixes=None,
        resilience: Optional[ResilienceOptions] = None) -> TrainState:
    """Train ``model`` from ``params`` over ``train_loader`` epochs.

    train_loader: iterable over epochs yielding dict batches (numpy,
    leading axis = global batch), exposing ``steps_per_epoch`` and
    ``batch_size`` (loader.py contract).

    ``resume=True`` (reference ``--resume``) restores params + optimizer
    state + step from ``prefix`` at ``begin_epoch``.

    ``profile_dir``: capture an XProf/perfetto device trace of steps 3–8 of
    the first epoch (the reference has no profiling subsystem — SURVEY §5
    calls this the free win; view with xprof/tensorboard).

    ``telemetry_dir``: stream structured run telemetry there (JSONL events
    + an end-of-run summary JSON — see ``mx_rcnn_tpu/telemetry``): the
    per-step wall-time breakdown (loader-wait / dispatch / metric-fetch
    stall / checkpoint-save), epoch wall time, and a recompile counter
    keyed on (program, batch bucket shape) so mixed-bucket epochs show
    their true compile cost.  Per-rank event files on multi-host; the
    summary is written by process 0 only (the ``profile_dir`` rank-split
    contract).  When a sink is already active (a driver configured one),
    it is reused and left open.  Disabled, every probe is a no-op sink
    call — one attribute check, zero allocations.

    ``steps_per_dispatch`` > 1 groups k consecutive loader batches and
    runs them through ONE dispatched ``lax.scan`` program
    (``make_multi_train_step``): amortizes per-dispatch overhead and lets
    XLA compile the step as a loop body — measured on v5-lite, the FPN
    step drops 21.95 → 17.85 ms inside the loop (better P2-conv layout;
    r4_tpu_session7.log).  On loaders exposing the ``wrap`` hook
    (AnchorLoader/ROIIter), group stacking AND the host→device transfer
    happen on the loader's prefetch thread (``_make_group_wrap``), so k>1
    keeps the same transfer/compute overlap as k=1; loaders without the
    hook fall back to consumer-side grouping with synchronous transfer.
    Groups must be shape-homogeneous, so every scale/orientation bucket
    change flushes the partial group through the single-step program
    (mixed-bucket epochs amortize less).  Math per step is identical
    (k=1 parity asserted; k>1 numeric parity vs a sequential driver is
    chaotic — discrete top-k/NMS flips amplify ulp differences — so k>1
    is covered structurally); per-step rng differs from the k=1 stream
    (keys are fold_in of one dispatch key), and metrics arrive as k-step
    means at dispatch granularity.  Epoch remainders smaller than k run
    through the single-step program.

    ``resilience`` (``ResilienceOptions``; all knobs off by default — a
    plain call compiles the exact same step program as before):

    * ``save_every_n_steps``: mid-epoch step checkpoints under
      ``{prefix}/steps`` at that batch cadence (always on a dispatch
      boundary; under an active NaN sentinel the due save forces a metric
      fetch first, so a step checkpoint is only ever written from
      verified-finite state).
    * ``auto_resume``: pick the furthest checkpoint — step or epoch —
      under ``prefix`` and continue from it.  Mid-epoch resume is EXACT
      on seed-deterministic loaders: the loader's RNG is advanced past
      the completed epochs (``advance_epochs``) and the resumed epoch's
      plan is generated in full then sliced (``skip_next``), the trainer
      RNG key is restored from the checkpoint, and the LR schedule counts
      restart at ``consumed`` against the epoch-rebased schedule — so the
      tail of the run is batch-for-batch identical to the uninterrupted
      one (k=1; k>1 regrouping at the resume point may differ around
      bucket flushes).
    * ``nan_policy``: the on-device all-finite sentinel is checked at
      every metric fetch.  ``halt`` dumps diagnostics and raises
      ``NonFiniteLossError``; ``skip`` counts (the step discarded the
      non-finite update in-graph, params were never poisoned);
      ``rollback`` restores the latest step checkpoint in-memory and
      keeps consuming the loader (the poisoned stretch contributes
      nothing; schedule counts resume from the checkpoint, so the LR
      step count lags by the rolled-back stretch — accepted).
    * SIGTERM/SIGINT during the epoch loop request a save at the next
      dispatch boundary and a clean return (``train/preempted``); ranks
      agree via allgather at lockstep fetch boundaries so orbax's save
      barriers never deadlock.
    """
    # thin-shard guard lives in make_train_step (mechanism level); eval's is
    # in Predictor.__init__ since it never builds a train step
    steps_per_epoch = train_loader.steps_per_epoch
    tel = telemetry.get()
    owns_tel = False
    if telemetry_dir and not tel.enabled:
        tel = telemetry.configure(
            telemetry_dir, rank=jax.process_index(),
            world=jax.process_count(),
            run_meta={"driver": "fit", "graph": graph,
                      "steps_per_dispatch": int(steps_per_dispatch),
                      "batch_size": train_loader.batch_size,
                      "steps_per_epoch": steps_per_epoch})
        owns_tel = True
    res = resilience if resilience is not None else ResilienceOptions()
    ckpt = (CheckpointManager(prefix, io_retries=res.max_io_retries,
                              io_backoff_s=res.io_backoff_s)
            if prefix else None)

    # auto-resume resolves the true starting position BEFORE the train
    # state exists: the LR schedule's boundaries are built relative to
    # begin_epoch, so the resolved epoch must feed make_optimizer
    begin0 = begin_epoch  # caller's begin (= the interrupted run's begin)
    step_resume = None  # (epoch, consumed) when resuming mid-epoch
    if res.auto_resume:
        if ckpt is None:
            raise ValueError("auto_resume requires a checkpoint prefix")
        point = ckpt.latest_resume_point()
        if point is None:
            logger.info("auto-resume: no checkpoint under %s — fresh start",
                        prefix)
        else:
            kind, r_ep, r_cons = point
            begin_epoch = r_ep
            if kind == "epoch":
                resume = True  # the legacy epoch-resume path below
                logger.info("auto-resume: epoch checkpoint %d under %s",
                            r_ep, prefix)
            else:
                step_resume = (r_ep, r_cons)
                logger.info("auto-resume: step checkpoint (epoch %d, "
                            "batch %d) under %s", r_ep, r_cons, prefix)

    state, tx, mask = create_train_state(cfg, params, steps_per_epoch,
                                   begin_epoch=begin_epoch,
                                   fixed_prefixes=fixed_prefixes)

    restored_key = None
    if resume:
        if ckpt is None:
            raise ValueError("resume=True requires a checkpoint prefix")
        abstract = jax.device_get(
            {"params": state.params, "opt_state": state.opt_state, "step": 0})
        r_params, r_opt, r_step = ckpt.load_epoch(
            begin_epoch, cfg, for_training=True, abstract_payload=abstract)
        if r_opt is not None:
            # the LR schedule was rebuilt with boundaries relative to
            # begin_epoch (make_lr_schedule), so its step count must restart
            # at 0 — only momentum buffers carry over.  Keeping the saved
            # global count would fire every LR drop begin_epoch epochs early.
            r_opt = _reset_schedule_counts(r_opt)
        state = TrainState(step=jax.numpy.asarray(r_step, jax.numpy.int32),
                           params=_runtime_owned(r_params),
                           opt_state=(_runtime_owned(r_opt)
                                      if r_opt is not None
                                      else state.opt_state))
        logger.info("resumed from %s epoch %d (step %d)", prefix, begin_epoch,
                    r_step)
    elif step_resume is not None:
        r_ep, r_cons = step_resume
        abstract = {"params": jax.device_get(state.params),
                    "opt_state": jax.device_get(state.opt_state),
                    "step": 0, "epoch": 0, "consumed": 0,
                    "rng_key": np.zeros((2,), np.uint32)}
        payload = ckpt.load_step_checkpoint(r_ep, r_cons,
                                            abstract_payload=abstract)
        r_opt = payload.get("opt_state")
        if r_opt is not None:
            # schedule rebuilt relative to r_ep; r_cons updates already
            # happened inside that epoch (see _reset_schedule_counts)
            r_opt = _reset_schedule_counts(r_opt, value=r_cons)
        state = TrainState(
            step=jax.numpy.asarray(payload["step"], jax.numpy.int32),
            params=_runtime_owned(payload["params"]),
            opt_state=(_runtime_owned(r_opt) if r_opt is not None
                       else state.opt_state))
        restored_key = payload.get("rng_key")
        logger.info("resumed mid-epoch from %s (epoch %d, batch %d, "
                    "step %d)", prefix, r_ep, r_cons, int(payload["step"]))

    if plan is not None:
        # multi-host: create the mesh's cross-process communicator NOW,
        # while ranks are aligned — its lazy creation inside the first
        # step would race the ranks' compile-time skew against the Gloo
        # key-exchange deadline (see warm_collectives; no-op otherwise)
        from mx_rcnn_tpu.parallel.distributed import warm_collectives

        warm_collectives(plan)
    step_fn = make_train_step(model, tx, plan=plan, graph=graph,
                              trainable_mask=mask, sentinel=res.sentinel,
                              skip_nonfinite=res.skip_nonfinite)
    k = int(steps_per_dispatch)
    multi_fn = (make_multi_train_step(model, tx, k, plan=plan, graph=graph,
                                      trainable_mask=mask,
                                      sentinel=res.sentinel,
                                      skip_nonfinite=res.skip_nonfinite)
                if k > 1 else None)
    # recompile tracking + device-prep program home: jit caches one
    # program per (step fn, bucket shape), so the first dispatch of each
    # pair is the compile.  The program registry mirrors that cache (fit
    # builds fresh step fns, so per-fit is exact), makes mixed-bucket
    # epochs show their true compile cost in the telemetry stream, and —
    # with a persistent program cache configured — accounts each first
    # dispatch as an AOT disk load vs an XLA compile.  Built BEFORE the
    # loader hooks so the device_prep program registers alongside the
    # step programs.
    from mx_rcnn_tpu.compile import ProgramRegistry

    registry = ProgramRegistry(cfg, dtype=cfg.tpu.COMPUTE_DTYPE
                               if cfg.tpu.COMPUTE_DTYPE in
                               ("float32", "bfloat16") else "float32",
                               plan=plan)

    # device-side preprocessing: when the config asks for it, the loader
    # is already emitting raw uint8 batches (+ raw_hw/flip sidecar keys)
    # and a DevicePrep hook must stand where device_put used to — raw
    # bytes reaching the step fn would be garbage.  Mesh plans raise in
    # maybe_device_prep (drivers strip the flag with a warning first).
    from mx_rcnn_tpu.data.device_prep import maybe_device_prep

    prep = maybe_device_prep(cfg, registry=registry, plan=plan)

    # device double-buffering: loaders that expose a ``put`` hook transfer
    # each batch from their prefetch thread (overlapping the previous
    # step's compute) instead of synchronously inside step dispatch; at
    # k>1 the ``wrap`` hook moves the whole group assembly (stacking +
    # stacked transfer) onto that thread instead.  fit OWNS both hooks:
    # they are (re)set every call so a loader reused across fit calls
    # with a different k/plan never runs a stale hook (a leftover group
    # wrap would feed tagged tuples to the k=1 path, and a leftover put
    # would re-transfer the wrap's already-on-device items).
    loader_wraps = False
    if hasattr(train_loader, "wrap"):
        train_loader.wrap = (_make_group_wrap(k, plan, prep=prep)
                             if k > 1 else None)
        loader_wraps = k > 1
    loader_puts = False
    if hasattr(train_loader, "put"):
        if k == 1 and not loader_wraps:
            if prep is not None:
                train_loader.put = prep.put
            else:
                train_loader.put = ((lambda b: shard_batch(plan, b))
                                    if plan is not None else jax.device_put)
            loader_puts = True
        else:  # the wrap transfers its own items — put must stay out
            train_loader.put = None
    if plan is not None and jax.process_count() > 1:
        # diagnose loader-partition misconfigurations at the contract
        # level, before they surface as an opaque jit shape mismatch: a
        # loader left at num_parts=1 on a multi-process mesh would yield a
        # self-consistent but process_count×-sized "global" batch
        # (round-4 advisor finding; the CLI drivers check this too, but
        # direct fit() callers bypassed them)
        from mx_rcnn_tpu.parallel.distributed import assert_loader_partition

        if hasattr(train_loader, "num_parts"):
            assert_loader_partition(plan, train_loader.batch_size,
                                    train_loader.num_parts,
                                    train_loader.part_index)
    n_chips = plan.n_data if plan else 1
    # multi-host (parallel/distributed.py): every process runs this same
    # loop over the global mesh in lockstep; only process 0 speaks/saves.
    # The loader carries its num_parts/part_index row slice; metrics are
    # replicated outputs, so the fetch below is a local read everywhere.
    proc0 = jax.process_index() == 0
    speedo = Speedometer(train_loader.batch_size, frequent=frequent,
                         n_chips=n_chips)
    speedo_cb = speedo if proc0 else (lambda *a, **k: None)
    bank = MetricBank()
    key = jax.random.PRNGKey(seed)
    if restored_key is not None:
        # the trainer key as it was at the interruption's save boundary:
        # the resumed per-step key stream continues bit-exactly
        key = jax.numpy.asarray(restored_key)

    # auto-resume loader fast-forward: burn the completed epochs' RNG
    # draws, then arm the resumed epoch's batch skip (the plan is drawn in
    # full and sliced, so the tail is identical to the uninterrupted run)
    if res.auto_resume and begin_epoch > begin0:
        if hasattr(train_loader, "advance_epochs"):
            train_loader.advance_epochs(begin_epoch - begin0)
        else:
            logger.warning("auto-resume: loader has no advance_epochs(); "
                           "the resumed epochs' schedules will replay the "
                           "loader's first-epoch RNG draws")
    if step_resume is not None:
        if not hasattr(train_loader, "skip_next"):
            raise ValueError(
                "auto_resume hit a mid-epoch step checkpoint but the "
                "loader has no skip_next() — cannot fast-forward "
                f"{type(train_loader).__name__} to batch {step_resume[1]}")
        train_loader.skip_next(step_resume[1])

    nan_at = nan_injection_step()  # env fault injection (fault_smoke.sh)

    profiling = False
    profiled = False
    if profile_dir and jax.process_count() > 1:
        # one trace dir per rank: on a shared filesystem the ranks' trace
        # writers would collide in a single directory (round-4 advisor
        # finding)
        import os

        profile_dir = os.path.join(profile_dir,
                                   f"rank{jax.process_index()}")
    def note_dispatch(fn_kind, shape):
        if registry.note_dispatch(f"train_{fn_kind}", shape):
            tel.counter("train/recompile")
            tel.meta("recompile", program=fn_kind, shape=list(shape))

    guard = PreemptionGuard()
    preempted = False
    last_saved = None  # (epoch, consumed) of the last written step ckpt

    def save_step_ckpt(ep, cur):
        """Step checkpoint of the CURRENT state (idempotent per position —
        a preemption landing on a just-saved boundary must not re-save
        into the same orbax key)."""
        nonlocal last_saved
        if last_saved == (ep, cur):
            return
        ckpt.save_step(ep, cur, state.params, cfg,
                       opt_state=state.opt_state,
                       step=int(jax.device_get(state.step)), rng_key=key)
        last_saved = (ep, cur)

    def handle_nonfinite(ep, cur, fetched):
        """The sentinel tripped at a fetch boundary — apply ``nan_policy``.
        Returns True when state was rolled back (the caller must suppress
        this boundary's step save)."""
        nonlocal state
        tel.counter("train/nan_detected")
        tel.meta("nan_detected", epoch=int(ep), consumed=int(cur),
                 policy=res.nan_policy)
        tel.dump_flight("nan_detected", epoch=int(ep), consumed=int(cur),
                        policy=res.nan_policy)
        logger.warning("non-finite loss/gradients detected (epoch %d, "
                       "batch %d, policy=%s)", ep, cur, res.nan_policy)
        if res.nan_policy == "skip":
            # the in-graph guard already discarded the bad update(s);
            # params were never poisoned — count and continue
            tel.counter("train/nan_skipped")
            return False
        if res.nan_policy == "halt":
            path = dump_nan_diagnostics(
                telemetry_dir or prefix, ep, cur,
                int(jax.device_get(state.step)), fetched)
            raise NonFiniteLossError(
                f"non-finite loss/gradients at epoch {ep}, batch {cur} "
                f"(policy=halt)"
                + (f"; diagnostics dumped to {path}" if path else ""))
        # rollback: restore the latest step checkpoint in-memory and keep
        # consuming the loader — the poisoned stretch contributes nothing
        # (schedule counts resume from the checkpoint, so the LR step
        # count lags by the rolled-back stretch; accepted)
        point = ckpt.latest_step_checkpoint() if ckpt is not None else None
        if point is None:
            raise NonFiniteLossError(
                f"non-finite loss/gradients at epoch {ep}, batch {cur} "
                f"(policy=rollback) with no step checkpoint to roll back "
                f"to — set save_every_n_steps (prefix: {prefix or 'none'})")
        g_ep, g_cons = point
        abstract = {"params": jax.device_get(state.params),
                    "opt_state": jax.device_get(state.opt_state),
                    "step": 0, "epoch": 0, "consumed": 0,
                    "rng_key": np.zeros((2,), np.uint32)}
        payload = ckpt.load_step_checkpoint(g_ep, g_cons,
                                            abstract_payload=abstract)
        r_opt = payload.get("opt_state")
        state = TrainState(
            step=jax.numpy.asarray(payload["step"], jax.numpy.int32),
            params=_runtime_owned(payload["params"]),
            opt_state=(_runtime_owned(r_opt) if r_opt is not None
                       else state.opt_state))
        tel.counter("train/nan_rollback")
        logger.warning("rolled back to step checkpoint (epoch %d, batch "
                       "%d)", g_ep, g_cons)
        return True

    with (guard if res.enabled else contextlib.nullcontext()):
      for epoch in range(begin_epoch, end_epoch):
        bank.reset()
        speedo.reset()
        pending = None
        buf = []
        # loader batches dispatched so far (a group item advances this by
        # k; profiling and metric cadence count batches).  A mid-epoch
        # resume starts the counters at the restored position — the
        # fast-forwarded loader yields exactly the tail.
        start_consumed = (step_resume[1]
                          if step_resume and epoch == begin_epoch else 0)
        consumed = start_consumed
        last_fetch = start_consumed
        last_step_save = start_consumed
        start_at = min(3, steps_per_epoch - 1)
        # epoch wall-time breakdown, telemetry-or-not (the epoch-end log
        # line reports wall/loader-wait either way; two perf_counter reads
        # per item is noise next to a dispatch)
        ep_t0 = time.perf_counter()
        loader_wait_s = 0.0
        it = iter(train_loader)
        while True:
            t_wait = time.perf_counter()
            try:
                item = next(it)
            except StopIteration:
                break
            dt_wait = time.perf_counter() - t_wait
            loader_wait_s += dt_wait
            tel.add("train/loader_wait", dt_wait)
            if (nan_at is not None and consumed == nan_at
                    and isinstance(item, dict)):
                # env fault injection (script/fault_smoke.sh): poison this
                # batch's images so the step's loss/grads go non-finite
                item = dict(item)
                item["images"] = item["images"] * np.float32("nan")
                logger.warning("fault injection: NaN images at batch %d "
                               "(MXR_FAULT_NAN_STEP)", consumed)
            if profile_dir and epoch == begin_epoch and not profiled:
                if not profiling and consumed >= start_at:
                    jax.profiler.start_trace(profile_dir)
                    profiling = True
                elif profiling and consumed >= 8:
                    # fence on state, not pending: a same-step cadence
                    # fetch can have consumed pending (cleared to None),
                    # and block_until_ready(None) returns immediately —
                    # truncating the trace tail.  state is always the
                    # latest dispatched step's output
                    jax.block_until_ready(state)
                    jax.profiler.stop_trace()
                    profiling = False
                    profiled = True
                    logger.info("wrote device trace to %s", profile_dir)
            t_disp = time.perf_counter()
            key, sub = jax.random.split(key)
            n_b = 1
            if loader_wraps:
                # producer-thread group assembly (_make_group_wrap):
                # items arrive tagged, already stacked AND on device —
                # the transfer overlapped the previous step's compute
                kind, n_b, data = item
                note_dispatch(kind, data["images"].shape)
                state, metrics = (multi_fn if kind == "group"
                                  else step_fn)(state, data, sub)
                pending = metrics
            elif multi_fn is None:
                batch = item
                if prep is not None and not loader_puts:
                    # loader without a put hook under device prep: the
                    # batch is still raw uint8 + sidecars — prep it here
                    # (synchronous; only hook-less wrapper loaders hit it)
                    batch = prep.put(batch)
                note_dispatch("single", batch["images"].shape)
                if plan is not None and not loader_puts:
                    batch = shard_batch(plan, batch)
                state, metrics = step_fn(state, batch, sub)
                pending = metrics
            else:
                # consumer-side fallback for loaders without the ``wrap``
                # hook: group k batches into one scanned dispatch (epoch
                # remainder < k runs through the single-step fn; bucket
                # changes flush the partial group — groups must be
                # shape-homogeneous)
                batch = item
                if buf and buf[0]["images"].shape != batch["images"].shape:
                    for b in buf:
                        key, sub = jax.random.split(key)
                        if prep is not None:
                            b = prep.put(b)
                        elif plan is not None:
                            b = shard_batch(plan, b)
                        note_dispatch("single", b["images"].shape)
                        state, metrics = step_fn(state, b, sub)
                    pending = metrics
                    buf = []
                buf.append(batch)
                if len(buf) == k:
                    stacked = jax.tree.map(lambda *xs: np.stack(xs), *buf)
                    if prep is not None:
                        stacked = prep.put_stacked(stacked)
                    elif plan is not None:
                        stacked = shard_stacked_batch(plan, stacked)
                    else:
                        stacked = jax.device_put(stacked)
                    note_dispatch("group", stacked["images"].shape)
                    state, metrics = multi_fn(state, stacked, sub)
                    pending = metrics
                    buf = []
            dt_disp = time.perf_counter() - t_disp
            tel.add("train/dispatch", dt_disp, n=n_b)
            # per-step latency distribution (dispatch wall over the group,
            # amortized per step) — the trainer's feed into the histogram
            # layer, so p99 step time is scrapeable live
            tel.observe("train/step_time", dt_disp / max(n_b, 1))
            cur = consumed + n_b
            # fetch metrics only at Speedometer cadence: a device→host scalar
            # read stalls the dispatch pipeline, so per-step reads would
            # serialize training.  A due step save under an active sentinel forces the
            # fetch first, so checkpoints only capture verified-finite state;
            # saves happen only with ``buf`` empty (pulled-not-dispatched
            # batches would desync the saved position from the state).
            save_due = (res.save_every_n_steps > 0 and ckpt is not None
                        and not buf
                        and cur - last_step_save >= res.save_every_n_steps)
            fetch_due = (cur - last_fetch >= frequent
                         or (save_due and res.sentinel))
            if fetch_due and pending is not None:
                with tel.span("train/fetch_stall"):
                    fetched = jax.device_get(pending)
                pending = None
                last_fetch = cur
                finite = fetched.pop("all_finite", None)
                bank.update(fetched)
                if finite is not None and finite < 1.0:
                    if handle_nonfinite(epoch, cur, fetched):
                        save_due = False  # just restored FROM a checkpoint
                        last_step_save = cur
            if save_due:
                save_step_ckpt(epoch, cur)
                last_step_save = cur
            # preemption: single-process reads the flag at every boundary;
            # multi-process must agree at deterministic lockstep points —
            # the fetch boundaries — or a rank saving alone would deadlock
            # orbax's cross-process barriers
            if jax.process_count() > 1:
                want_stop = (preemption_agreed(guard.requested)
                             if fetch_due else False)
            else:
                want_stop = guard.requested
            if want_stop and not buf:
                if ckpt is not None:
                    save_step_ckpt(epoch, cur)
                tel.counter("train/preempted")
                # flight-record the shutdown at the safe boundary (the
                # signal handler's own dump has no step context)
                tel.dump_flight("preempted", epoch=epoch,
                                consumed=int(cur))
                preempted = True
            for j in range(n_b):
                speedo_cb(epoch, consumed + j, bank.format())
            consumed += n_b
            if preempted:
                break
        if buf:  # epoch remainder (< k) — flushed AFTER the loop so the
            # drain cannot depend on steps_per_epoch matching the
            # iterator's true yield count (wrapper loaders may differ)
            t_disp = time.perf_counter()
            for b in buf:
                key, sub = jax.random.split(key)
                if prep is not None:
                    b = prep.put(b)
                elif plan is not None:
                    b = shard_batch(plan, b)
                note_dispatch("single", b["images"].shape)
                state, metrics = step_fn(state, b, sub)
            pending = metrics
            dt_disp = time.perf_counter() - t_disp
            tel.add("train/dispatch", dt_disp, n=len(buf))
            tel.observe("train/step_time", dt_disp / max(len(buf), 1))
            buf = []
        if profiling:  # epoch shorter than the stop step: close the trace
            jax.block_until_ready(state)  # pending may be fetched-and-None
            jax.profiler.stop_trace()
            profiling = False
            logger.info("wrote device trace to %s", profile_dir)
        if pending is not None:
            with tel.span("train/fetch_stall"):
                fetched = jax.device_get(pending)
            finite = fetched.pop("all_finite", None)
            bank.update(fetched)
            if finite is not None and finite < 1.0:
                handle_nonfinite(epoch, consumed, fetched)
        ep_wall = time.perf_counter() - ep_t0
        tel.add("train/epoch", ep_wall)
        tel.counter("train/steps", consumed - start_consumed)
        # tuned-pipeline tripwire: a saturated input pipeline keeps the
        # consumer's loader wait ≈ 0; spending more than the threshold
        # fraction of epoch wall blocked on the loader means the tuned
        # (k, workers, prefetch) cell no longer hides host work on this
        # box — surfaced as a counter + meta event so perf triage and the
        # pipeline sweep read the same signal.  Needs a few steps of
        # signal: a 1–2 step epoch is all warmup, not steady state.
        ep_steps = consumed - start_consumed
        wait_frac = loader_wait_s / max(ep_wall, 1e-9)
        if ep_steps >= 8 and wait_frac > LOADER_WAIT_TRIPWIRE_FRAC:
            tel.counter("train/loader_wait_tripwire")
            tel.meta("loader_wait_tripwire", epoch=epoch,
                     frac=round(wait_frac, 4),
                     loader_wait_s=round(loader_wait_s, 3),
                     wall_s=round(ep_wall, 3))
            if proc0:
                logger.warning(
                    "input pipeline not saturated: loader_wait %.1fs is "
                    "%.0f%% of epoch wall (threshold %.0f%%) — retune with "
                    "python -m mx_rcnn_tpu.train.pipeline --auto-tune",
                    loader_wait_s, 100 * wait_frac,
                    100 * LOADER_WAIT_TRIPWIRE_FRAC)
        if proc0:
            # wall + loader-wait on the one-line epoch summary: single-log
            # triage of "slow epoch — device or input pipeline?" without
            # opening the JSONL
            logger.info("Epoch[%d] Train-%s\tWall=%.1fs LoaderWait=%.1fs",
                        epoch, bank.format().replace("\t", " Train-"),
                        ep_wall, loader_wait_s)
        if preempted:
            if proc0:
                logger.info("preemption requested — exiting cleanly after "
                            "step checkpoint (epoch %d, batch %d); rerun "
                            "with auto_resume to continue", epoch, consumed)
            break
        if ckpt is not None:
            # multi-host: EVERY rank calls save — orbax's CheckpointManager
            # runs its own cross-process barriers inside save() and writes
            # from the primary host only (ranks must share one prefix on a
            # shared filesystem).  Gating this on rank 0 deadlocks orbax's
            # sync_global_devices (found by the two-process CLI drive).
            # State leaves are replicated (DP) so device_get is local.
            with tel.span("train/checkpoint_save"):
                ckpt.save_epoch(epoch + 1, state.params, cfg,
                                opt_state=state.opt_state,
                                step=int(jax.device_get(state.step)))
    if jax.process_count() > 1:
        # align ranks before returning: after the last collective nothing
        # else synchronizes them, and a rank that exits the process much
        # later than its peers trips the jax.distributed SHUTDOWN barrier
        # deadline under load (observed with Gloo on a contended host)
        from mx_rcnn_tpu.parallel.distributed import sync

        sync("fit_end")
    if owns_tel:
        # every rank streams its own event file; only process 0 writes the
        # aggregated summary (the profile_dir rank-split contract) — the
        # cross-rank fold is scripts/telemetry_report.py's job
        if proc0:
            path = tel.write_summary()
            logger.info("wrote telemetry summary to %s", path)
        telemetry.shutdown()
    return state
