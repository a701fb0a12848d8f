#!/usr/bin/env python
"""Online serving driver: checkpoint → warmed ServeEngine → HTTP frontend.

The online counterpart of test.py's offline loop (ROADMAP north star:
"serves heavy traffic"): load a checkpoint (or ``--synthetic`` random
weights for smoke/CI), pre-compile every (bucket, batch) program, then
serve ``/predict`` with bucket-aware dynamic batching until SIGTERM/SIGINT.

    # smoke: synthetic weights, tiny buckets, TCP on 8321
    python serve.py --network resnet50 --synthetic --port 8321 \
        --cfg "tpu__SCALES=((96,128),)" --serve-batch 4 --max-delay-ms 20

    # production-shaped: real checkpoint, telemetry on
    python serve.py --network resnet101 --prefix model/e2e --epoch 10 \
        --port 8321 --serve-batch 8 --max-delay-ms 10 --telemetry-dir /tmp/t

    # self-healing plane: 2 supervised replicas behind a router, rolling
    # checkpoint hot-reload as training writes new saves
    python serve.py --network resnet101 --prefix model/e2e --epoch 10 \
        --port 8321 --replicas 2 --watch-checkpoints model/e2e

    # cross-host fabric (ISSUE 12): a router that members join over TCP
    python serve.py --fabric --port 8320                  # the router
    python serve.py --network resnet50 --synthetic --port 8321 \
        --join 127.0.0.1:8320                             # a member

Scale-out contract (``--replicas N``): the parent builds NO model — it
runs the ReplicaSupervisor + ReplicaRouter (serve/supervisor.py) over N
child processes of this same script (``--replica-index I``, internal),
each a full Predictor→engine→HTTP stack on its own Unix socket.  Replica
failure is a 503-shed + retry-on-alternate + backoff respawn; SIGTERM
drains gracefully and a SECOND SIGTERM hard-aborts (flight dump +
SIGKILL the children) so a wedged drain can never hang shutdown.  At
``--replicas 1`` (default) behavior is unchanged from before the plane
existed.
"""

from __future__ import annotations

import argparse
import atexit
import os
import signal
import tempfile
import threading

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.compile import setup_compile_cache
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.tools.common import (add_common_args, apply_program_cache,
                                      config_from_args,
                                      eval_params_from_args,
                                      start_observability)


def parse_args(argv=None):
    parser = argparse.ArgumentParser(
        description="Serve a Faster R-CNN network over HTTP")
    add_common_args(parser, train=False)
    parser.add_argument("--port", type=int, default=0,
                        help="TCP port for the HTTP frontend")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--unix-socket", default="", dest="unix_socket",
                        help="serve HTTP over this Unix socket instead of "
                             "TCP (tests, local sidecars)")
    parser.add_argument("--serve-batch", type=int, default=4,
                        dest="serve_batch",
                        help="images per forward — every batch is padded "
                             "to exactly this size (one program per "
                             "bucket)")
    parser.add_argument("--max-delay-ms", type=float, default=10.0,
                        dest="max_delay_ms",
                        help="flush a partial batch once its oldest "
                             "request has waited this long; THE latency/"
                             "throughput knob (0 = no coalescing wait)")
    parser.add_argument("--serve-e2e", action="store_true",
                        dest="serve_e2e",
                        help="single-dispatch serving: stage raw uint8 on "
                             "the caller thread and run device prep + "
                             "forward + decode/NMS as ONE fused program "
                             "per (bucket, batch, dtype) — one host→device"
                             " transfer, one dispatch, one (B, cap, 6) "
                             "readback per batch.  Off (default) "
                             "reproduces the classic host-prep path "
                             "byte-for-byte")
    parser.add_argument("--stream", action="store_true",
                        help="enable POST /stream sequenced-frame "
                             "streaming (single-process mode only): "
                             "per-stream state over the same batcher, so "
                             "same-bucket frames from different streams "
                             "coalesce into shared dispatches")
    parser.add_argument("--stream-skip-thresh", type=float, default=0.0,
                        dest="stream_skip_thresh",
                        help="frame-delta skip gate: mean absolute uint8 "
                             "pixel delta (on-device, vs the stream's "
                             "reference frame) below which a frame "
                             "answers with cached detections and no "
                             "forward.  0 (default) disables the gate — "
                             "gate-off streaming is byte-identical to "
                             "per-frame /predict")
    parser.add_argument("--stream-max-skip", type=int, default=30,
                        dest="stream_max_skip",
                        help="force a full forward after this many "
                             "consecutive skips, bounding detection "
                             "staleness on static scenes")
    parser.add_argument("--max-queue", type=int, default=64,
                        dest="max_queue",
                        help="bounded-queue backpressure: submits beyond "
                             "this many pending requests get 503")
    parser.add_argument("--deadline-ms", type=float, default=30000.0,
                        dest="deadline_ms",
                        help="default per-request deadline (504 when "
                             "exceeded; requests may override; <=0 "
                             "disables)")
    parser.add_argument("--target-p99-ms", type=float, default=0.0,
                        dest="target_p99_ms",
                        help="enable the SLO controller: adapt per-bucket "
                             "flush batch/delay toward this end-to-end "
                             "request-time p99 and shed load (503) when "
                             "the queue trend predicts misses (0 = off)")
    parser.add_argument("--slo-interval-ms", type=float, default=500.0,
                        dest="slo_interval_ms",
                        help="SLO controller tick period")
    parser.add_argument("--slo-window-s", type=float, default=10.0,
                        dest="slo_window_s",
                        help="trailing window the controller's p99 is "
                             "computed over")
    parser.add_argument("--replicas", type=int, default=1,
                        help="run N supervised engine replicas behind a "
                             "router (1 = the classic single-process "
                             "server, unchanged)")
    parser.add_argument("--replica-index", type=int, default=-1,
                        dest="replica_index",
                        help=argparse.SUPPRESS)  # internal: child mode
    parser.add_argument("--replica-devices", default="",
                        dest="replica_devices",
                        help="semicolon-separated device groups, one per "
                             "replica (group i lands in child env "
                             "MXR_REPLICA_DEVICES for the deployment "
                             "image to map onto TPU_VISIBLE_CHIPS / "
                             "CUDA_VISIBLE_DEVICES)")
    parser.add_argument("--watch-checkpoints", default="",
                        dest="watch_checkpoints",
                        help="poll this checkpoint prefix (PR-2 layout: "
                             "epoch dirs + steps/) and hot-reload new "
                             "generations with zero downtime — rolling "
                             "across replicas, canary-gated, rollback on "
                             "non-finite outputs")
    parser.add_argument("--watch-interval-s", type=float, default=5.0,
                        dest="watch_interval_s",
                        help="checkpoint watcher poll period")
    # -- cross-host fabric (ISSUE 12) — all opt-in; the fork-based
    # --replicas path is untouched when none of these are passed
    parser.add_argument("--fabric", action="store_true",
                        help="run the cross-host fabric router: remote "
                             "members join via --join/--pool-file//admin/"
                             "register; with --replicas N local fork "
                             "children serve alongside them")
    parser.add_argument("--pool-file", default="", dest="pool_file",
                        help="seed fabric membership from this file (one "
                             "HOST:PORT or unix socket path per line; "
                             "implies --fabric)")
    parser.add_argument("--join", default="",
                        help="run as a fabric MEMBER: serve on --port and "
                             "register with the fabric router at this "
                             "HOST:PORT once warm")
    parser.add_argument("--advertise", default="",
                        help="address to advertise to the router on "
                             "--join (default: --host:--port — set this "
                             "when members sit behind NAT/containers)")
    parser.add_argument("--hedge-after-ms", type=float, default=0.0,
                        dest="hedge_after_ms",
                        help="router tail hedging: duplicate a request "
                             "still unanswered after this long to a "
                             "second member and take the first 2xx "
                             "(0 = off)")
    parser.add_argument("--partition-floor", type=float, default=0.5,
                        dest="partition_floor",
                        help="ready-member fraction below which the "
                             "router flight-dumps fabric_partition (it "
                             "keeps serving the reachable subset "
                             "regardless)")
    parser.add_argument("--probe-interval-s", type=float, default=1.0,
                        dest="probe_interval_s",
                        help="fabric membership probe period")
    # -- elastic autoscaling (ISSUE 18) — OFF by default: without
    # --autoscale no CapacityAuthority is ever constructed and the
    # fabric serves the fixed fleet byte-for-byte as before
    parser.add_argument("--autoscale", action="store_true",
                        help="run the capacity authority on the fabric "
                             "router: forecast demand from queue-depth "
                             "trends and scale the fleet between "
                             "--autoscale-min/--autoscale-max by "
                             "unparking drained members, admitting "
                             "standbys, or forking local replicas — "
                             "never recompiling (capacity warms from "
                             "the shared AOT cache)")
    parser.add_argument("--autoscale-min", type=int, default=1,
                        dest="autoscale_min",
                        help="fleet floor: never drain below this many "
                             "capacity members")
    parser.add_argument("--autoscale-max", type=int, default=4,
                        dest="autoscale_max",
                        help="fleet ceiling: never grow past this many "
                             "capacity members")
    parser.add_argument("--autoscale-target-depth", type=float,
                        default=4.0, dest="autoscale_target_depth",
                        help="target utilization: forecast demand "
                             "(queue depth + inflight) per ready member "
                             "above which the fleet grows; scale-down "
                             "needs sustained load below half of it")
    parser.add_argument("--autoscale-interval-s", type=float, default=1.0,
                        dest="autoscale_interval_s",
                        help="capacity authority tick period")
    parser.add_argument("--autoscale-standby", default="",
                        dest="autoscale_standby",
                        help="comma-separated member addresses the "
                             "authority may admit when demand outgrows "
                             "the registered fleet (parked members are "
                             "always preferred — they are already warm)")
    # -- data flywheel request capture (ISSUE 13) — OFF by default: the
    # engine keeps its NULL capture sink (zero hot-path work) unless a
    # capture dir is configured
    parser.add_argument("--capture-dir", default="", dest="capture_dir",
                        help="spill sampled request captures (staged "
                             "pixels + detections + score stats, PII-free)"
                             " as atomic JSONL+npz shards here for the "
                             "flywheel miner (off when unset)")
    parser.add_argument("--capture-sample", type=int, default=1,
                        dest="capture_sample",
                        help="capture every Nth served request")
    parser.add_argument("--capture-bytes", type=int, default=256 << 20,
                        dest="capture_bytes",
                        help="capture-dir byte budget: oldest shard pairs "
                             "rotate out beyond this")
    parser.add_argument("--capture-shard-records", type=int, default=32,
                        dest="capture_shard_records",
                        help="records per spilled shard pair")
    parser.add_argument("--capture-member", default=None,
                        dest="capture_member",
                        help="fleet member id folded into shard/manifest "
                             "names when several members share one "
                             "capture dir (default: hostname)")
    # -- multi-model serving (ISSUE 15) — all opt-in; without --models
    # the single-model boot path is byte-for-byte unchanged
    parser.add_argument("--models", default="",
                        help="serve SEVERAL models from one process: "
                             "comma-separated ID=NETWORK entries (e.g. "
                             "'box=resnet50,mask=resnet101').  Requests "
                             "route with /predict?model=ID (default: the "
                             "first entry); each model gets its own "
                             "config, Predictor, program registry/AOT "
                             "subtree, bucket queues, and SLO controller. "
                             "Single-process mode only")
    parser.add_argument("--model-arg", action="append", default=[],
                        dest="model_arg", metavar="ID:KEY=VALUE",
                        help="per-model override, repeatable.  KEYs: "
                             "prefix, epoch (checkpoint source), "
                             "cfg (an extra --cfg style PATH=VALUE), "
                             "pin (1 = never page this model's weights "
                             "out), weight (scheduling/SLO class, "
                             "default 1.0), target-p99-ms (per-model SLO "
                             "controller target; overrides the global "
                             "--target-p99-ms), fidelity ('cascade' "
                             "[default] gates through --cascade, 'full' "
                             "pins the tenant to the big model "
                             "unconditionally)")
    # -- cascade serving (ISSUE 19) — opt-in; without --cascade no router
    # is built and the --models pool serves byte-for-byte as before
    parser.add_argument("--cascade", default="", metavar="SMALL:BIG",
                        help="accuracy-aware model cascade over two "
                             "--models entries: every gated request "
                             "first hits SMALL; frames whose on-device "
                             "confidence-gate hardness (the flywheel "
                             "miner's definition) clears --cascade-thresh "
                             "escalate to BIG — the staged pixels are "
                             "reused, never re-staged, and escalated "
                             "frames feed the capture ring tagged "
                             "cascade_escalated.  Requires --models and "
                             "--serve-e2e")
    parser.add_argument("--cascade-thresh", type=float, default=0.5,
                        dest="cascade_thresh",
                        help="escalation threshold in [0, 1] of the "
                             "hardness scale: 0 escalates every frame "
                             "(big-only answers), 1 none (small-only). "
                             "Calibrate against the live hardness "
                             "histogram on /metrics (cascade.latency."
                             "hardness_p50)")
    parser.add_argument("--weight-budget-mb", type=float, default=0.0,
                        dest="weight_budget_mb",
                        help="device weight-residency byte budget for "
                             "--models: param trees beyond it are paged "
                             "host<->device (LRU by last dispatch, "
                             "pinned models exempt).  0 = unbounded")
    # -- distributed request tracing (ISSUE 16) — OFF by default: every
    # hop keeps the NULL tracer (one attribute check, zero span work)
    parser.add_argument("--trace", action="store_true",
                        help="enable distributed request tracing: mint/"
                             "accept X-Mxr-Trace contexts at the frontend,"
                             " record per-hop spans (router pick/hedge/"
                             "retry, pool sched, stream gate, engine "
                             "batch-causality) to spans_<member>.jsonl "
                             "under --trace-dir, tail-sample slow/errored "
                             "trees to trace_tail_<member>.jsonl; query "
                             "with scripts/trace_query.py")
    parser.add_argument("--trace-dir", default="", dest="trace_dir",
                        help="span-file directory (default: "
                             "--telemetry-dir; one of the two is required "
                             "with --trace)")
    parser.add_argument("--trace-sample", type=float, default=1.0,
                        dest="trace_sample",
                        help="fraction of frontend-minted traces that are "
                             "sampled (client-sent contexts keep their "
                             "own sampled flag)")
    parser.add_argument("--trace-tail-budget", type=int, default=256,
                        dest="trace_tail_budget",
                        help="kept slow/errored span trees in the tail "
                             "ring (oldest evicted beyond this)")
    # -- watchtower alerting (ISSUE 20) — OFF by default: without
    # --watch/--alert-rules no Watchtower is ever constructed — no
    # monitor thread, no metric-history ring, and /metrics + the
    # telemetry JSONL stream are byte-for-byte the watch-off output
    parser.add_argument("--watch", action="store_true",
                        help="run the watchtower: evaluate the alert-rule "
                             "pack (telemetry/rules_default.json unless "
                             "--alert-rules) against live telemetry every "
                             "--watch-tick-s — SLO error-budget burn "
                             "rates, thresholds, absence, trends; alerts "
                             "surface on /alerts, /metrics "
                             "(mxr_alert_state), and alerts_<member>."
                             "jsonl, and a newly-firing alert "
                             "flight-dumps with recent tail trace ids "
                             "attached.  On the fabric router, rules with "
                             "scope=fleet evaluate per member")
    parser.add_argument("--alert-rules", default="", dest="alert_rules",
                        help="alert-rule pack JSON to evaluate (implies "
                             "--watch); a bad pack is a clean boot error "
                             "naming the offending rule")
    parser.add_argument("--watch-tick-s", type=float, default=1.0,
                        dest="watch_tick_s",
                        help="watchtower evaluation tick period")
    return parser.parse_args(argv)


def _configure_tracing(args, member: str, rank: int = 0) -> None:
    """--trace → an active tracer for this process; without the flag,
    honor the MXR_TRACE_DIR env opt-in (subprocess members inherit it),
    else leave the NULL tracer in place.  Closed via atexit so the tail
    ring and spans stream land on every normal exit path."""
    from mx_rcnn_tpu.telemetry import tracectx

    if getattr(args, "trace", False):
        out_dir = args.trace_dir or args.telemetry_dir
        if not out_dir:
            raise SystemExit("--trace needs --trace-dir or "
                             "--telemetry-dir")
        tracectx.configure(out_dir, member=member, rank=rank,
                           sample=args.trace_sample,
                           tail_budget=args.trace_tail_budget)
        atexit.register(tracectx.shutdown)
        logger.info("tracing: spans_%s.jsonl under %s (sample=%.2f)",
                    member, out_dir, args.trace_sample)
    elif tracectx.configure_from_env(member=member, rank=rank) is not None:
        atexit.register(tracectx.shutdown)


def _build_watch(args, member: str, **providers):
    """--watch/--alert-rules → a started :class:`Watchtower` for this
    process, else None — and None means NOTHING was constructed: no
    monitor thread, no history ring, no alert log.  ``providers`` are
    the per-mode sampling closures (summary_fn/hists_fn on an engine
    process, fleet_fn/summaries_fn on the fabric router).  A bad rule
    pack is a clean boot error naming the offending rule."""
    if not (getattr(args, "watch", False) or
            getattr(args, "alert_rules", "")):
        return None
    from mx_rcnn_tpu.telemetry.watch import (RuleError, WatchOptions,
                                             Watchtower, load_rules)

    try:
        rules = (load_rules(args.alert_rules) if args.alert_rules
                 else None)
        watch = Watchtower(
            rules=rules, member=member,
            opts=WatchOptions(interval_s=args.watch_tick_s),
            out_dir=args.telemetry_dir or None, **providers)
    except (RuleError, ValueError, OSError) as e:
        raise SystemExit(f"--alert-rules: {e}")
    watch.start()
    return watch


def parse_model_specs(models: str, model_args) -> list:
    """``--models a=resnet50,b=vgg16`` + repeated ``--model-arg
    ID:KEY=VALUE`` → ordered spec dicts (first entry = default model)."""
    specs = []
    by_id = {}
    for entry in models.split(","):
        entry = entry.strip()
        if not entry:
            continue
        mid, _, network = entry.partition("=")
        mid, network = mid.strip(), network.strip()
        if not mid or not network:
            raise SystemExit(f"--models entries are ID=NETWORK, got "
                             f"{entry!r}")
        if mid in by_id:
            raise SystemExit(f"--models: duplicate model id {mid!r}")
        spec = {"id": mid, "network": network, "prefix": None,
                "epoch": None, "cfg": [], "pin": False, "weight": 1.0,
                "target_p99_ms": None, "fidelity": "cascade"}
        by_id[mid] = spec
        specs.append(spec)
    for arg in model_args or []:
        mid, sep, kv = arg.partition(":")
        key, sep2, val = kv.partition("=")
        if not sep or not sep2 or mid.strip() not in by_id:
            raise SystemExit(f"--model-arg is ID:KEY=VALUE with ID from "
                             f"--models, got {arg!r}")
        spec, key = by_id[mid.strip()], key.strip().replace("-", "_")
        if key == "cfg":
            spec["cfg"].append(val)
        elif key == "pin":
            spec["pin"] = val.strip().lower() in ("1", "true", "yes")
        elif key == "weight":
            spec["weight"] = float(val)
        elif key == "target_p99_ms":
            spec["target_p99_ms"] = float(val)
        elif key == "fidelity":
            spec["fidelity"] = val.strip()
        elif key in ("prefix", "epoch"):
            spec[key] = int(val) if key == "epoch" else val
        else:
            raise SystemExit(f"--model-arg: unknown key {key!r}")
    if not specs:
        raise SystemExit("--models parsed to zero entries")
    return specs


def _install_signals(done: threading.Event, hard_cleanup=None):
    """First SIGTERM/SIGINT = graceful drain (flight-record + set
    ``done``); the SECOND = hard abort — flight dump, SIGKILL any child
    replicas, ``os._exit`` — so a wedged drain can't hang shutdown."""
    state = {"armed": False}

    def _on_signal(signum, frame):
        name = signal.Signals(signum).name
        if state["armed"]:
            telemetry.get().dump_flight("hard_abort", signal=name)
            logger.error("second %s: hard abort", name)
            if hard_cleanup is not None:
                try:
                    hard_cleanup()
                except Exception:  # noqa: BLE001 — exiting anyway
                    pass
            os._exit(130)
        state["armed"] = True
        telemetry.get().dump_flight("preempt_signal", signal=name)
        done.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _on_signal)


def _build_engine(args, cfg, external: bool = False):
    """checkpoint → Predictor → started ServeEngine (single + replica
    paths share this; the supervisor parent never builds one).
    ``external=True`` (multi-model pool mode) skips the engine's own
    dispatcher thread — the ModelPool flushes it instead."""
    from mx_rcnn_tpu.eval import Predictor
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.serve import ServeEngine, ServeOptions
    from mx_rcnn_tpu.tools.common import calibrate_from_args

    apply_program_cache(args)  # before the Predictor builds its registry
    # start-up's split: each phase timed once, onto /metrics' "setup"
    with telemetry.stage("setup/model") as t_model:
        model = build_model(cfg)
    with telemetry.stage("setup/params") as t_params:
        params = eval_params_from_args(args, cfg, model)
    # --calibrate-shard: activation scales from the FLOAT params, persisted
    # next to the AOT markers BEFORE the Predictor quantizes its copy
    act_scales = calibrate_from_args(args, cfg, model, params)
    with telemetry.stage("setup/predictor") as t_predictor:
        predictor = Predictor(model, params, cfg, dtype=args.infer_dtype,
                              act_scales=act_scales)
    engine = ServeEngine(predictor, cfg, ServeOptions(
        batch_size=args.serve_batch, max_delay_ms=args.max_delay_ms,
        max_queue=args.max_queue, deadline_ms=args.deadline_ms,
        # the common --loader-workers flag doubles as the serving prep
        # pool size (same data/workers.py pool, image-only tasks)
        prep_workers=args.loader_workers or 0,
        serve_e2e=getattr(args, "serve_e2e", False)))
    if getattr(args, "capture_dir", ""):
        from mx_rcnn_tpu.flywheel import CaptureOptions, RequestCapture

        engine.capture = RequestCapture(CaptureOptions(
            capture_dir=args.capture_dir,
            sample_every=args.capture_sample,
            shard_records=args.capture_shard_records,
            byte_budget=args.capture_bytes,
            member=getattr(args, "capture_member", None)))
    engine.setup.update(model_s=t_model.seconds, params_s=t_params.seconds,
                        predictor_s=t_predictor.seconds)
    engine.start(external=external)
    return predictor, engine


def _build_pool(args):
    """--models → a started :class:`ModelPool`: per model, its own
    config/Predictor/engine (external-dispatch) + per-model warmup, one
    cross-model dispatcher, LRU weight residency under
    --weight-budget-mb, and a per-model SLO controller when a p99 target
    is set.  Returns (pool, streams, cascade) — streams only under
    --stream, cascade (a warmed CascadeRouter) only under --cascade."""
    from mx_rcnn_tpu.serve import (CascadeRouter, ControllerOptions,
                                   ModelPool, SLOController, StreamManager,
                                   StreamOptions, warmup)

    specs = parse_model_specs(args.models, args.model_arg)
    pool = ModelPool(
        budget_bytes=int(args.weight_budget_mb * (1 << 20)))
    pool.start()
    streams = {}
    for i, spec in enumerate(specs):
        margs = argparse.Namespace(**vars(args))
        margs.network = spec["network"]
        margs.cfg = list(args.cfg) + list(spec["cfg"])
        if spec["prefix"] is not None:
            margs.prefix = spec["prefix"]
        if spec["epoch"] is not None:
            margs.epoch = spec["epoch"]
        if i > 0:
            # one capture sink per process: shard files are not
            # model-namespaced, so only the default model captures
            margs.capture_dir = ""
        cfg = config_from_args(margs, train=False)
        predictor, engine = _build_engine(margs, cfg, external=True)
        target = spec["target_p99_ms"]
        if target is None and args.target_p99_ms > 0:
            target = args.target_p99_ms
        controller = None
        if target:
            controller = SLOController(engine, ControllerOptions(
                target_p99_ms=target,
                interval_s=args.slo_interval_ms / 1e3,
                window_s=args.slo_window_s, label=spec["id"]))
        pool.add_model(spec["id"], cfg, predictor, engine,
                       controller=controller, pinned=spec["pin"],
                       weight=spec["weight"], fidelity=spec["fidelity"])
        # warm THIS model before building the next: the most recent
        # owning registry points the process-global jax compilation
        # cache at its dtype dir, so compiles must land while their
        # model's registry is the active one for AOT markers to agree
        # with where the executables persisted
        warmup(engine)
        if args.stream:
            sm = StreamManager(engine, StreamOptions(
                skip_thresh=args.stream_skip_thresh,
                max_skip=args.stream_max_skip))
            sm.warmup()
            streams[spec["id"]] = sm
        if controller is not None:
            controller.start()
    cascade = None
    if getattr(args, "cascade", ""):
        small, sep, big = args.cascade.partition(":")
        small, big = small.strip(), big.strip()
        if not sep or not small or not big:
            raise SystemExit(f"--cascade is SMALL:BIG with ids from "
                             f"--models, got {args.cascade!r}")
        try:
            cascade = CascadeRouter(pool, small, big,
                                    thresh=args.cascade_thresh)
        except (KeyError, ValueError) as e:
            raise SystemExit(f"--cascade: {e}")
        # ready the gate program now, after the per-model warmups — a
        # cascade boot compiles everything before mark_ready, so the
        # steady state (and the zero-recompile contract) covers the gate
        cascade.warmup()
        pool.cascade = cascade
        if small in streams:
            # cascade-route the small model's streams: hard frames of a
            # camera escalate exactly like hard /predict images
            streams[small].cascade = cascade
        logger.info("cascade: %s -> %s at thresh %.3f (gate program "
                    "warm)", small, big, args.cascade_thresh)
    return pool, streams, cascade


def main_single(args):
    """The classic single-process server (--replicas 1), plus optional
    in-process checkpoint hot-reload when --watch-checkpoints is set."""
    from mx_rcnn_tpu.serve import (CheckpointWatcher, ControllerOptions,
                                   SLOController, StreamManager,
                                   StreamOptions, make_server,
                                   reload_engine_params, warmup)

    if not args.unix_socket and not args.port:
        raise SystemExit("pass --port or --unix-socket")
    cfg = config_from_args(args, train=False)
    # the plane owns the sink (configure → summary → shutdown) and, with
    # --obs-port, the live Prometheus endpoint; the frontend's own
    # /metrics keeps serving regardless (JSON + ?format=prom)
    obs = start_observability(args, "serve",
                              run_meta={"network": args.network,
                                        "serve_batch": args.serve_batch,
                                        "max_delay_ms": args.max_delay_ms},
                              configure_telemetry=True)
    _configure_tracing(args, "server")
    predictor, engine = _build_engine(args, cfg)
    warmup(engine)
    stream = None
    if args.stream:
        stream = StreamManager(engine, StreamOptions(
            skip_thresh=args.stream_skip_thresh,
            max_skip=args.stream_max_skip))
        # gate on: ready the per-bucket frame_delta programs now, like
        # warmup() readied the forwards — steady-state streaming never
        # compiles, and a warm AOT cache covers the gate too
        stream.warmup()
    controller = None
    if args.target_p99_ms > 0:
        controller = SLOController(engine, ControllerOptions(
            target_p99_ms=args.target_p99_ms,
            interval_s=args.slo_interval_ms / 1e3,
            window_s=args.slo_window_s)).start()

    watcher = None
    if args.watch_checkpoints:
        def _reload(target):
            ok, info = reload_engine_params(
                engine, predictor, cfg,
                dict(target, generation=engine.generation + 1))
            return ok

        watcher = CheckpointWatcher(args.watch_checkpoints, _reload,
                                    interval_s=args.watch_interval_s)
        watcher.start()

    # watchtower over THIS engine: summary counters/gauges feed the
    # history ring, the engine's live latency hists feed burn rules
    from mx_rcnn_tpu.telemetry.obs import engine_summary
    watch = _build_watch(
        args, "server",
        summary_fn=lambda: engine_summary(engine),
        hists_fn=lambda: {**telemetry.get().live_hists(),
                          **engine.latency_hists()})

    server = make_server(engine, port=args.port or None, host=args.host,
                         unix_socket=args.unix_socket or None,
                         stream=stream, watch=watch)
    # serve_forever on a worker thread; the main thread parks on an event
    # the signal handlers set — shutdown() called from the serving thread
    # itself would deadlock its poll loop
    done = threading.Event()
    _install_signals(done)
    t = threading.Thread(target=server.serve_forever, name="serve-http",
                         daemon=True)
    t.start()
    where = args.unix_socket or f"http://{args.host}:{args.port}"
    logger.info("serving %s on %s (batch=%d, max_delay=%.0fms, "
                "max_queue=%d)", args.network, where, args.serve_batch,
                args.max_delay_ms, args.max_queue)
    done.wait()
    logger.info("shutting down: %s", engine.metrics()["counters"])
    server.shutdown()
    if watch is not None:
        watch.stop()  # no alert churn from the drain itself
    if watcher is not None:
        watcher.stop()
    if controller is not None:
        controller.stop()
    engine.stop()
    extra = {"serve": engine.metrics()}
    if watch is not None:
        extra["watch"] = watch.state()
    obs.close(extra=extra)


def main_multimodel(args):
    """One process, N models (--models): a ModelPool behind the single
    frontend — zero-recompile per-model routing, cross-model batch
    interleaving, bounded weight residency, per-model SLO isolation."""
    from mx_rcnn_tpu.serve import make_server

    if not args.unix_socket and not args.port:
        raise SystemExit("pass --port or --unix-socket")
    obs = start_observability(args, "serve",
                              run_meta={"models": args.models,
                                        "serve_batch": args.serve_batch,
                                        "max_delay_ms": args.max_delay_ms},
                              configure_telemetry=True)
    _configure_tracing(args, "server")
    pool, streams, cascade = _build_pool(args)
    default = pool.default_model
    server = make_server(pool.engine_for(default),
                         port=args.port or None, host=args.host,
                         unix_socket=args.unix_socket or None,
                         stream=streams.get(default), pool=pool,
                         streams=streams, cascade=cascade)
    done = threading.Event()
    _install_signals(done)
    t = threading.Thread(target=server.serve_forever, name="serve-http",
                         daemon=True)
    t.start()
    where = args.unix_socket or f"http://{args.host}:{args.port}"
    logger.info("serving %d model(s) %s on %s (batch=%d, weight budget "
                "%.0f MB)", len(pool.model_ids()), pool.model_ids(),
                where, args.serve_batch, args.weight_budget_mb)
    done.wait()
    logger.info("shutting down: %s", pool.metrics()["pool"])
    server.shutdown()
    pool.stop()
    obs.close(extra={"serve": pool.metrics()})


def main_replica(args):
    """One supervised replica child (--replica-index I, internal): the
    full engine stack over the supervisor-assigned Unix socket, folding
    its telemetry as rank I+1 of a (replicas+1)-world so the parent's
    obs plane aggregates per-replica snapshots (the PR-5 mechanism)."""
    from mx_rcnn_tpu.serve import serve_replica

    assert args.unix_socket, "--replica-index requires --unix-socket"
    cfg = config_from_args(args, train=False)
    obs = start_observability(args, "serve",
                              rank=args.replica_index + 1,
                              world=max(args.replicas, 1) + 1,
                              run_meta={"network": args.network,
                                        "replica": args.replica_index},
                              configure_telemetry=True)
    _configure_tracing(args, f"member{args.replica_index}",
                       rank=args.replica_index + 1)
    predictor, engine = _build_engine(args, cfg)
    done = threading.Event()
    _install_signals(done)
    try:
        serve_replica(engine, cfg, args.unix_socket,
                      index=args.replica_index, predictor=predictor,
                      done=done)
    finally:
        obs.close(extra={"serve": engine.metrics()})


def main_plane(args):
    """The supervisor parent (--replicas N > 1): no model, no device —
    spawn N replica children, route /predict across the ready ones,
    respawn the dead, roll checkpoint generations through them."""
    import sys

    from mx_rcnn_tpu.serve import (CheckpointWatcher, ReplicaRouter,
                                   ReplicaSupervisor, make_router_server,
                                   replica_specs)

    if not args.unix_socket and not args.port:
        raise SystemExit("pass --port or --unix-socket")
    obs = start_observability(args, "serve", rank=0,
                              world=args.replicas + 1,
                              run_meta={"network": args.network,
                                        "replicas": args.replicas},
                              configure_telemetry=True)
    _configure_tracing(args, "router")
    sock_dir = tempfile.mkdtemp(prefix="mxr_replicas_")
    specs = replica_specs(sys.argv, args.replicas, sock_dir,
                          devices=args.replica_devices)
    sup = ReplicaSupervisor(specs)
    # no orphans: children die with the parent on EVERY exit path —
    # normal drain, exception, or the hard-abort signal escalation
    atexit.register(sup.sweep)
    done = threading.Event()
    _install_signals(done, hard_cleanup=lambda: sup.sweep(0.0))
    sup.start()
    router = ReplicaRouter(sup)
    server = make_router_server(router, port=args.port or None,
                                host=args.host,
                                unix_socket=args.unix_socket or None)
    watcher = None
    if args.watch_checkpoints:
        watcher = CheckpointWatcher(args.watch_checkpoints, sup.reload_to,
                                    interval_s=args.watch_interval_s)
        watcher.start()
    t = threading.Thread(target=server.serve_forever, name="router-http",
                         daemon=True)
    t.start()
    where = args.unix_socket or f"http://{args.host}:{args.port}"
    logger.info("serving plane: %d replica(s) behind %s (sockets under "
                "%s)", args.replicas, where, sock_dir)
    # park until a signal OR systemic failure (every replica FAILED)
    while not done.is_set():
        if sup.broken.wait(timeout=0.5):
            break
        if done.wait(timeout=0.5):
            break
    broken = sup.broken.is_set() and not done.is_set()
    logger.info("plane shutting down: %s", sup.metrics()["counters"])
    server.shutdown()
    if watcher is not None:
        watcher.stop()
    sup.stop()
    obs.close(extra={"replica_plane": sup.metrics()})
    if broken:
        raise SystemExit("serving plane is down: every replica crossed "
                         "the respawn limit (see flight dumps)")


def main_member(args):
    """A standalone fabric member (--join): the full engine stack over
    TCP, self-registering with the fabric router once warm.  Reloads
    arrive from the ROUTER's rolling ``/admin/reload`` — a member never
    watches checkpoints itself, or a roll would double-swap it."""
    import sys  # noqa: F401 — parallel to the other mains

    from mx_rcnn_tpu.serve import serve_replica

    if not args.unix_socket and not args.port:
        raise SystemExit("pass --port (or --unix-socket) for a fabric "
                         "member")
    cfg = config_from_args(args, train=False)
    index = int(os.environ.get("MXR_REPLICA_INDEX", "0"))
    obs = start_observability(args, "serve",
                              run_meta={"network": args.network,
                                        "join": args.join,
                                        "member_index": index},
                              configure_telemetry=True)
    _configure_tracing(args, f"member{index}", rank=index)
    predictor, engine = _build_engine(args, cfg)
    done = threading.Event()
    _install_signals(done)
    try:
        serve_replica(engine, cfg,
                      sock_path=args.unix_socket or None,
                      port=args.port or None, host=args.host,
                      index=index, predictor=predictor, done=done,
                      join=args.join, advertise=args.advertise or None)
    finally:
        obs.close(extra={"serve": engine.metrics()})


def main_fabric(args):
    """The fabric router (--fabric / --pool-file): probe-driven
    membership over remote TCP members (plus local fork children when
    --replicas N > 1), least-loaded routing, breakers, hedging, and
    rolling cross-member hot reload."""
    import sys

    from mx_rcnn_tpu.serve import (CheckpointWatcher, FabricOptions,
                                   FabricRouter, ReplicaPool,
                                   ReplicaSupervisor, make_fabric_server,
                                   replica_specs)

    if not args.unix_socket and not args.port:
        raise SystemExit("pass --port or --unix-socket")
    obs = start_observability(args, "serve",
                              run_meta={"network": args.network,
                                        "fabric": True,
                                        "replicas": args.replicas},
                              configure_telemetry=True)
    _configure_tracing(args, "router")
    pool = ReplicaPool(FabricOptions(
        probe_interval_s=args.probe_interval_s,
        hedge_after_ms=args.hedge_after_ms,
        partition_floor=args.partition_floor))
    done = threading.Event()
    sup = None
    if args.replicas > 1:
        sock_dir = tempfile.mkdtemp(prefix="mxr_replicas_")
        specs = replica_specs(sys.argv, args.replicas, sock_dir,
                              devices=args.replica_devices)
        sup = ReplicaSupervisor(specs)
        atexit.register(sup.sweep)
        _install_signals(done, hard_cleanup=lambda: sup.sweep(0.0))
        sup.start()
        pool.adopt_supervisor(sup)
    else:
        _install_signals(done)
    if args.pool_file:
        n = pool.load_pool_file(args.pool_file)
        logger.info("fabric: seeded %d member address(es) from %s",
                    n, args.pool_file)
    pool.start()
    router = FabricRouter(pool)
    authority = None
    if args.autoscale:
        from mx_rcnn_tpu.serve import AutoscalerOptions, CapacityAuthority
        standby = [a.strip()
                   for a in args.autoscale_standby.split(",") if a.strip()]
        authority = CapacityAuthority(
            pool, supervisor=sup, standby=standby,
            opts=AutoscalerOptions(
                min_members=args.autoscale_min,
                max_members=args.autoscale_max,
                target_depth=args.autoscale_target_depth,
                interval_s=args.autoscale_interval_s)).start()
        router.autoscaler = authority
    # watchtower over the FLEET: the pool folds to the per-member view
    # (absence/threshold rules), peer telemetry snapshots feed
    # fleet-scoped burn rules, and the router's own fabric/route_time
    # hist (observed only while a watchtower is attached) feeds local
    # burn rules on routed latency
    watch = None
    if args.watch or args.alert_rules:
        from mx_rcnn_tpu.telemetry.obs import read_peer_snapshots
        from mx_rcnn_tpu.telemetry.watch import fleet_from_pool

        summaries_fn = None
        if args.telemetry_dir:
            tdir = args.telemetry_dir
            summaries_fn = (lambda: {
                f"rank{r}": s
                for r, s in read_peer_snapshots(tdir)[0].items()})
        watch = _build_watch(args, "router",
                             fleet_fn=lambda: fleet_from_pool(pool),
                             summaries_fn=summaries_fn)
        router.watchtower = watch
    server = make_fabric_server(router, port=args.port or None,
                                host=args.host,
                                unix_socket=args.unix_socket or None)
    watcher = None
    if args.watch_checkpoints:
        watcher = CheckpointWatcher(args.watch_checkpoints,
                                    pool.reload_to,
                                    interval_s=args.watch_interval_s)
        watcher.start()
    t = threading.Thread(target=server.serve_forever, name="fabric-http",
                         daemon=True)
    t.start()
    where = args.unix_socket or f"http://{args.host}:{args.port}"
    logger.info("fabric router on %s (%d seeded member(s), %d local "
                "replica(s))", where, len(pool.members),
                args.replicas if sup is not None else 0)
    done.wait()
    logger.info("fabric shutting down: %s", pool.counters)
    server.shutdown()
    if watch is not None:
        watch.stop()  # no alert churn from the drain itself
    if authority is not None:
        authority.stop()  # no scale decisions during teardown
    if watcher is not None:
        watcher.stop()
    pool.stop()
    if sup is not None:
        sup.stop()
    extra = {"fabric": pool.metrics()}
    if authority is not None:
        extra["autoscale"] = authority.state()
    if watch is not None:
        extra["watch"] = watch.state()
    obs.close(extra=extra)


def choose_mode(args) -> str:
    """argv → serving mode.  Order is a contract: child replicas first
    (never recurse into a plane), then the opt-in fabric paths, then the
    PR-8 fork plane, else the classic single server.  With none of the
    fabric flags set, dispatch is EXACTLY the pre-fabric decision tree —
    the fork path cannot be perturbed by dormant fabric code."""
    if args.replica_index >= 0:
        return "replica"
    if getattr(args, "fabric", False) or getattr(args, "pool_file", ""):
        return "fabric"
    if getattr(args, "join", ""):
        return "member"
    if args.replicas > 1:
        return "plane"
    return "single"


def main(args):
    setup_compile_cache()
    mode = choose_mode(args)
    if getattr(args, "cascade", "") and not getattr(args, "models", ""):
        raise SystemExit("--cascade routes between two --models entries; "
                         "pass --models SMALL=...,BIG=... (and "
                         "--serve-e2e)")
    if getattr(args, "models", ""):
        # the pool shares one device owner (its dispatcher thread); the
        # multi-process planes each bind a full device stack per child,
        # so --models composes with none of them (yet)
        if mode != "single":
            raise SystemExit(f"--models requires single-process mode "
                             f"(got mode {mode!r})")
        return main_multimodel(args)
    if getattr(args, "stream", False) and mode != "single":
        # stream state (reference frames, seq high-water marks) lives in
        # ONE engine's process; routing frames of a stream across
        # replicas/members would silently break the skip gate and seq
        # ordering, so refuse rather than degrade
        raise SystemExit(
            f"--stream requires single-process mode (got mode "
            f"{mode!r}: drop --replicas/--fabric/--join/--pool-file "
            f"or run one streaming server per device)")
    return {"replica": main_replica, "fabric": main_fabric,
            "member": main_member, "plane": main_plane,
            "single": main_single}[choose_mode(args)](args)


if __name__ == "__main__":
    main(parse_args())
