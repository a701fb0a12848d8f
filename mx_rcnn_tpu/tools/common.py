"""Shared driver plumbing: dataset construction, param init/loading, mesh
setup — the glue the reference spreads across ``train_end2end.py:train_net``
and ``rcnn/tools/*`` (load_param, generate_config calls, ctx parsing).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Optional, Tuple

import jax
import numpy as np

from mx_rcnn_tpu.config import Config, generate_config, list_datasets, list_networks
from mx_rcnn_tpu.data import SyntheticDataset
from mx_rcnn_tpu.data.pascal_voc import PascalVOC
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.models import build_model, init_params
from mx_rcnn_tpu.parallel import MeshPlan, make_mesh
from mx_rcnn_tpu.train.checkpoint import load_params_npz
from mx_rcnn_tpu.train.resilience import (add_resilience_args,
                                          inject_roidb_faults)


def add_common_args(parser: argparse.ArgumentParser, train: bool = True):
    """The reference's shared argparse surface (names kept; GPU-specific
    flags get TPU equivalents)."""
    parser.add_argument("--network", default="resnet101", choices=list_networks())
    parser.add_argument("--dataset", default="PascalVOC", choices=list_datasets())
    parser.add_argument("--image_set", default=None,
                        help="override the preset image set")
    parser.add_argument("--root_path", default="data")
    parser.add_argument("--dataset_path", default=None)
    parser.add_argument("--prefix", default="model/e2e",
                        help="checkpoint prefix (directory for orbax)")
    # TPU equivalents of --gpus/--ctx: how many mesh devices to use
    parser.add_argument("--devices", type=int, default=0,
                        help="data-mesh size; 0 = all visible devices")
    # zero-data-on-disk mode (no reference counterpart)
    parser.add_argument("--synthetic", action="store_true",
                        help="use the synthetic dataset (no files needed)")
    parser.add_argument("--synthetic_images", type=int, default=64)
    parser.add_argument("--cfg", action="append", default=[],
                        metavar="PATH=VALUE",
                        help="config override, repeatable; double-underscore "
                             "paths into the tree with python-literal values "
                             "(e.g. --cfg tpu__SCALES='((64,96),)' "
                             "--cfg TRAIN__BATCH_ROIS=32)")
    parser.add_argument("--loader-workers", type=int, default=None,
                        dest="loader_workers", metavar="N",
                        help="host input-pipeline worker processes "
                             "(data/workers.py): N > 0 fans decode/resize/"
                             "flip over N processes with shared-memory "
                             "handover, batches bit-identical to the "
                             "default serial producer (0)")
    parser.add_argument("--telemetry-dir", default="", dest="telemetry_dir",
                        help="stream structured run telemetry here (JSONL "
                             "events + summary JSON; per-rank files on "
                             "multi-host, summary from process 0 only — "
                             "fold with scripts/telemetry_report.py)")
    parser.add_argument("--obs-port", type=int, default=0, dest="obs_port",
                        metavar="PORT",
                        help="live observability endpoint: rank 0 serves "
                             "GET /metrics (Prometheus text, all ranks "
                             "folded from --telemetry-dir snapshots) and "
                             "/healthz on 127.0.0.1:PORT while the run is "
                             "alive (telemetry/obs.py; 0 = off, no "
                             "network bind)")
    if train:
        # multi-host (the reference's unscripted KVStore('dist_sync') tier,
        # scripted here — parallel/distributed.py): every process runs the
        # same command with its own --dist-process-id; --dist-auto on TPU
        # pods.  Train-only: eval drivers reject these at argparse level
        # (multi-process eval is not supported — run it single-process).
        parser.add_argument("--dist-auto", action="store_true",
                            help="join a TPU-pod distributed runtime "
                                 "(topology auto-detected)")
        parser.add_argument("--dist-coordinator", default=None,
                            metavar="HOST:PORT",
                            help="distributed coordinator address "
                                 "(non-pod multi-host)")
        parser.add_argument("--dist-num-processes", type=int, default=None)
        parser.add_argument("--dist-process-id", type=int, default=None)
        parser.add_argument("--pretrained", default="",
                            help=".npz backbone/params path (converted)")
        parser.add_argument("--pretrained_epoch", type=int, default=0)
        parser.add_argument("--begin_epoch", type=int, default=0)
        parser.add_argument("--end_epoch", type=int, default=10)
        parser.add_argument("--lr", type=float, default=None)
        parser.add_argument("--lr_step", default=None,
                            help="comma-separated epochs, e.g. '7'")
        parser.add_argument("--frequent", type=int, default=20)
        parser.add_argument("--no_flip", action="store_true")
        parser.add_argument("--no_shuffle", action="store_true")
        parser.add_argument("--resume", action="store_true")
        parser.add_argument("--batch_images", type=int, default=None,
                            help="GLOBAL images per step (default: 1 per device)")
        parser.add_argument("--seed", type=int, default=0,
                            help="train RNG seed (sampling streams + "
                                 "dropout); loader shuffle uses its own")
        parser.add_argument("--num-steps", type=int, default=0, dest="num_steps",
                            help="cap steps per epoch (smoke runs)")
        parser.add_argument("--steps-per-dispatch", type=int, default=1,
                            help="train steps per dispatched program "
                                 "(lax.scan grouping; >1 amortizes dispatch "
                                 "overhead and lets XLA compile the step as "
                                 "a loop body — see train/trainer.py fit "
                                 "docstring; applies to every fit-based "
                                 "driver, alternate stages included)")
        parser.add_argument("--prefetch", type=int, default=None,
                            metavar="DEPTH",
                            help="host→device prefetch queue depth "
                                 "(tpu.PREFETCH; default from config)")
        parser.add_argument("--device-prep", action="store_true",
                            dest="device_prep",
                            help="run the per-sample resize/flip/normalize/"
                                 "pad hot path on device as a jitted "
                                 "program (data/device_prep.py; default "
                                 "off = host numpy path, bit-identical to "
                                 "previous releases)")
        parser.add_argument("--tuned-pipeline", action="store_true",
                            dest="tuned_pipeline",
                            help="boot into the input-pipeline cell "
                                 "persisted by `python -m mx_rcnn_tpu.train."
                                 "pipeline --auto-tune` (k steps/dispatch, "
                                 "loader workers, prefetch depth, "
                                 "device-prep); "
                                 "explicit flags win per field")
        # data flywheel replay (ISSUE 13): mix mined serving captures
        # into the epoch plan (data/replay.py); the mix is drawn from the
        # loader's plan RNG, so --auto-resume reproduces it bit-for-bit
        parser.add_argument("--replay-manifest", default="",
                            dest="replay_manifest",
                            help="mined-<digest>.json manifest from "
                                 "flywheel.py mine; enables replay mixing")
        parser.add_argument("--replay-ratio", type=float, default=0.25,
                            dest="replay_ratio",
                            help="fraction of each batch's slots "
                                 "substituted with replay records "
                                 "(in [0, 1); only with --replay-manifest)")
        parser.add_argument("--replay-thresh", type=float, default=0.5,
                            dest="replay_thresh",
                            help="min served detection score kept as a "
                                 "replay pseudo-label")
        # fault tolerance (train/resilience.py): --save-every-n-steps,
        # --auto-resume, --nan-policy on every fit-based driver
        add_resilience_args(parser)
    else:
        parser.add_argument("--epoch", type=int, default=10,
                            help="checkpoint epoch to load")
        parser.add_argument("--vis", action="store_true")
        parser.add_argument("--thresh", type=float, default=1e-3)
        parser.add_argument("--infer-dtype", default="float32",
                            dest="infer_dtype",
                            choices=["float32", "bfloat16", "int8",
                                     "int8-activation"],
                            help="inference variant: float32 (exact), "
                                 "bfloat16 (params cast, outputs back to "
                                 "f32 — tolerance-pinned parity vs f32), "
                                 "int8 (symmetric weight quantization), "
                                 "or int8-activation (weights int8 AND "
                                 "network-input activations fake-quantized"
                                 " against scales calibrated with "
                                 "--calibrate-shard).  Each dtype gets its"
                                 " own program-registry key space and "
                                 "persistent-cache dir")
        parser.add_argument("--calibrate-shard", type=int, default=0,
                            dest="calibrate_shard", metavar="N",
                            help="int8-activation calibration: run the "
                                 "FLOAT model over N held-out images "
                                 "(tail of the eval set; deterministic "
                                 "noise under --synthetic), record per-"
                                 "tensor activation absmax scales, and "
                                 "persist them next to the AOT marker "
                                 "manifest in the program cache (0 = use "
                                 "previously persisted scales, or degrade "
                                 "to weight-only int8)")
        parser.add_argument("--device-prep", action="store_true",
                            dest="device_prep",
                            help="run eval preprocessing (resize/"
                                 "normalize/pad) on device as a jitted "
                                 "program — the loader ships staged raw "
                                 "uint8 and the Predictor preps it in the "
                                 "prefetch-thread transfer hook (same "
                                 "host-bilinear parity pin as train; "
                                 "single-mesh only — mesh plans raise)")
        parser.add_argument("--program-cache", default="",
                            dest="program_cache", metavar="DIR",
                            help="persistent compiled-program cache base "
                                 "dir (same as the MXR_PROGRAM_CACHE env "
                                 "var): a second boot over a warm dir "
                                 "loads its XLA programs from disk "
                                 "instead of recompiling (machine-, "
                                 "jax-version- and dtype-keyed subdirs; "
                                 "see README 'Program registry')")
    return parser


def apply_program_cache(args) -> None:
    """Fold ``--program-cache`` into the ``MXR_PROGRAM_CACHE`` env var
    (the single knob the :class:`ProgramRegistry` reads) before any
    Predictor/registry is built.  The flag wins over an inherited env."""
    import os

    if getattr(args, "program_cache", ""):
        os.environ["MXR_PROGRAM_CACHE"] = args.program_cache


def parse_cfg_overrides(items) -> dict:
    """``--cfg PATH=VALUE`` (python-literal) → overrides dict.  Shared by
    the CLI drivers and the pipeline tuner so the syntax and error
    messages stay identical everywhere."""
    import ast

    overrides = {}
    for item in items or []:
        key, _, val = item.partition("=")
        if not _:
            raise ValueError(f"--cfg expects PATH=VALUE, got '{item}'")
        try:
            overrides[key] = ast.literal_eval(val)
        except (ValueError, SyntaxError) as e:
            raise ValueError(
                f"--cfg {key}: value {val!r} is not a python literal "
                f"(strings need quotes, e.g. --cfg dataset__IMAGE_SET="
                f"'\"2007_trainval\"'): {e}") from None
    return overrides


def config_from_args(args, train: bool = True) -> Config:
    overrides = parse_cfg_overrides(getattr(args, "cfg", []))
    if getattr(args, "loader_workers", None) is not None:
        overrides["tpu__LOADER_WORKERS"] = int(args.loader_workers)
    if getattr(args, "prefetch", None) is not None:
        overrides["tpu__PREFETCH"] = int(args.prefetch)
    if getattr(args, "device_prep", False):
        overrides["tpu__DEVICE_PREP"] = True
    if train:
        if args.lr is not None:
            overrides["TRAIN__LR"] = args.lr
        if args.lr_step is not None:
            overrides["TRAIN__LR_STEP"] = tuple(
                int(e) for e in str(args.lr_step).split(","))
        if getattr(args, "no_flip", False):
            overrides["TRAIN__FLIP"] = False
        if getattr(args, "no_shuffle", False):
            overrides["TRAIN__SHUFFLE"] = False
    cfg = generate_config(args.network, args.dataset, **overrides)
    if args.image_set:
        # train drivers read IMAGE_SET; test-mode drivers (test.py, reeval,
        # demo) read TEST_IMAGE_SET via get_imdb(test=True) — the override
        # must land on the field the driver actually consumes
        field = "IMAGE_SET" if train else "TEST_IMAGE_SET"
        cfg = cfg.replace(dataset=dataclasses.replace(
            cfg.dataset, **{field: args.image_set}))
    if args.dataset_path:
        cfg = cfg.replace(dataset=dataclasses.replace(
            cfg.dataset, DATASET_PATH=args.dataset_path))
    if args.synthetic:
        # from-scratch-friendly: normalize pixel scale (pretrained weights
        # absorb it in the reference contract; random init cannot)
        cfg = cfg.replace(network=dataclasses.replace(
            cfg.network, PIXEL_STDS=(127.0, 127.0, 127.0)))
    if train and getattr(args, "tuned_pipeline", False):
        # boot into the persisted tuned pipeline cell (python -m
        # mx_rcnn_tpu.train.pipeline --auto-tune).  Looked up AFTER every
        # other override is applied — the tuned key is a
        # tuned-field-normalized digest of exactly this config.
        from mx_rcnn_tpu.train.pipeline import apply_tuned_to_args

        cfg = apply_tuned_to_args(args, cfg)
    return cfg


def strip_device_prep_for_mesh(cfg: Config, plan) -> Config:
    """Device-side preprocessing is single-mesh only for now (the prep
    output would need the plan's input sharding) — drivers downgrade to
    the host path with a warning instead of fit raising mid-boot."""
    if plan is not None and getattr(cfg.tpu, "DEVICE_PREP", False):
        logger.warning("--device-prep is not supported under a mesh plan "
                       "yet — using the host preprocessing path")
        cfg = cfg.replace(tpu=dataclasses.replace(cfg.tpu,
                                                  DEVICE_PREP=False))
    return cfg


def get_imdb(args, cfg: Config, test: bool = False):
    """Dataset factory (reference: the imdb dispatch in train/test drivers)."""
    if args.synthetic:
        s = cfg.tpu.SCALES[0]
        return SyntheticDataset(num_images=args.synthetic_images,
                                num_classes=cfg.NUM_CLASSES,
                                height=s[0], width=s[1])
    name = cfg.dataset.DATASET
    image_set = cfg.dataset.TEST_IMAGE_SET if test else cfg.dataset.IMAGE_SET
    if name == "PascalVOC":
        return PascalVOC(image_set, args.root_path, cfg.dataset.DATASET_PATH)
    if name == "coco":
        from mx_rcnn_tpu.data.coco_dataset import COCODataset

        return COCODataset(image_set, args.root_path, cfg.dataset.DATASET_PATH)
    raise KeyError(name)


def get_train_roidb(imdb, cfg: Config, roidb=None):
    """gt (or a pre-built ``roidb``, e.g. with proposals attached) → flip →
    filter.  Proposal attachment must happen BEFORE this: flipping mirrors
    the ``proposals`` key."""
    if roidb is None:
        roidb = imdb.gt_roidb()
    if cfg.TRAIN.FLIP:
        roidb = imdb.append_flipped_images(roidb)
    # env-driven fault injection (MXR_FAULT_BAD_RECORD; no-op when unset)
    # AFTER filtering: the corrupted record must survive into the epoch
    # plan for script/fault_smoke.sh to exercise the loader's isolation
    return inject_roidb_faults(imdb.filter_roidb(roidb))


def replay_from_args(args, cfg: Config):
    """``--replay-manifest`` → (replay_roidb, replay_ratio) loader kwargs.

    Returns ``(None, 0.0)`` when replay is off or the manifest mined
    nothing usable (an empty round must not fail the training run)."""
    manifest = getattr(args, "replay_manifest", "")
    if not manifest:
        return None, 0.0
    from mx_rcnn_tpu.data.replay import ReplayDataset

    ds = ReplayDataset(manifest, cfg.NUM_CLASSES,
                       min_score=getattr(args, "replay_thresh", 0.5))
    roidb = ds.gt_roidb()
    if not roidb:
        logger.warning("replay manifest %s yielded no usable records "
                       "(all pseudo-labels below --replay-thresh?) — "
                       "training without replay", manifest)
        return None, 0.0
    logger.info("replay: mixing %d mined record(s) from %s at ratio %.2f",
                len(roidb), manifest, args.replay_ratio)
    return roidb, float(args.replay_ratio)


def init_dist_from_args(args) -> tuple:
    """``--dist-*`` → ``init_distributed``; returns (process_index,
    process_count).  Must run before anything queries devices."""
    from mx_rcnn_tpu.parallel import init_distributed

    return init_distributed(
        coordinator_address=getattr(args, "dist_coordinator", None),
        num_processes=getattr(args, "dist_num_processes", None),
        process_id=getattr(args, "dist_process_id", None),
        auto=getattr(args, "dist_auto", False))


def make_plan(args) -> Optional[MeshPlan]:
    n = args.devices if args.devices > 0 else len(jax.devices())
    if n <= 1:
        return None
    return make_mesh(jax.devices()[:n], data=n)


def setup_parallel(args):
    """Distributed rendezvous (``--dist-*``) THEN mesh plan — in that
    order, since the plan must see the global topology.  Returns
    ``(plan, process_index, process_count)``; every train driver that
    supports multi-host goes through here so the flags can never be
    silently ignored."""
    pidx, pcount = init_dist_from_args(args)
    plan = make_plan(args)
    if pcount > 1 and plan is None:
        raise ValueError(
            "multi-process run resolved to a single-device plan; pass "
            "--devices covering every host's devices (or 0 for all)")
    return plan, pidx, pcount


def start_observability(args, driver: str, rank: int = 0, world: int = 1,
                        run_meta: Optional[dict] = None,
                        configure_telemetry: bool = False):
    """Build the driver's :class:`~mx_rcnn_tpu.telemetry.obs.ObsPlane`
    from the common flags.  Inert (zero binds, zero threads, NULL
    telemetry untouched) unless ``--obs-port`` is set — or
    ``configure_telemetry=True`` and ``--telemetry-dir`` is set, for
    drivers whose sink isn't owned by ``fit`` (test/serve/bench): the
    plane then also owns configure/summary/shutdown.  Call ``close()``
    (ideally in a finally) when the run ends."""
    from mx_rcnn_tpu.telemetry.obs import ObsPlane

    meta = {"driver": driver, **(run_meta or {})}
    return ObsPlane(port=getattr(args, "obs_port", 0) or 0,
                    telemetry_dir=getattr(args, "telemetry_dir", "") or "",
                    rank=rank, world=world, run_meta=meta,
                    configure_telemetry=configure_telemetry)


def check_dist_loader(plan, batch_size: int, pcount: int, pidx: int) -> None:
    """Multi-host loader sanity: the contiguous ``num_parts`` slice must be
    the rows this process's mesh shards hold (no-op single-process)."""
    if pcount > 1:
        from mx_rcnn_tpu.parallel import assert_loader_partition

        assert_loader_partition(plan, batch_size, pcount, pidx)


def init_or_load_params(args, cfg: Config, model, batch_size: int,
                        key=None):
    """Random-init params, then overlay pretrained weights if given
    (reference load_param + Normal-init of new heads in train_net)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    del batch_size  # init shapes don't depend on it
    params = init_params(model, cfg, key, batch_size=1)
    if args.pretrained:
        path = args.pretrained
        if not os.path.exists(path):
            raise FileNotFoundError(path)
        loaded = load_params_npz(path)
        params = _overlay(params, loaded)
        logger.info("loaded pretrained params from %s", path)
    return params


def _overlay(params, loaded):
    """Copy leaves from ``loaded`` into ``params`` where paths+shapes match
    (partial restore: backbone-only checkpoints leave heads at init)."""
    import jax.numpy as jnp

    def walk(dst, src, path=""):
        out = {}
        for k, v in dst.items():
            if isinstance(v, dict):
                out[k] = walk(v, src.get(k, {}), path + k + "/")
            elif k in src and np.shape(src[k]) == np.shape(v):
                out[k] = jnp.asarray(src[k])
            else:
                if k in src:
                    logger.warning("shape mismatch at %s%s: %s vs %s — kept init",
                                   path, k, np.shape(src[k]), np.shape(v))
                out[k] = v
        return out

    return walk(params, loaded)


class CappedLoader:
    """Wraps a loader to at most ``n`` steps per epoch (smoke runs).

    Forwards the resilience fast-forward API (``advance_epochs`` /
    ``skip_next``) so ``--num-steps`` smoke runs still auto-resume: a
    skip of ``m`` consumed batches shrinks THIS wrapper's next epoch to
    ``n - m`` yields, keeping the epoch end at the same global position
    the uninterrupted capped run would have reached."""

    def __init__(self, inner, n: int):
        self._inner = inner
        self._n = n
        self._skip = 0
        self.batch_size = inner.batch_size

    @property
    def steps_per_epoch(self) -> int:
        return min(self._n, self._inner.steps_per_epoch)

    def __len__(self):
        return self.steps_per_epoch

    def advance_epochs(self, n: int) -> None:
        self._inner.advance_epochs(n)

    def skip_next(self, m: int) -> None:
        self._inner.skip_next(m)
        self._skip = m

    # fit() owns the loader put/wrap hooks; proxy them to the wrapped
    # loader so a capped run keeps producer-thread transfer/group
    # assembly (k>1 dispatch groups and device-prep both ride these) —
    # without the proxy fit would fall back to synchronous consumer-side
    # handling for every --num-steps run.
    @property
    def put(self):
        return getattr(self._inner, "put", None)

    @put.setter
    def put(self, v):
        self._inner.put = v

    @property
    def wrap(self):
        return getattr(self._inner, "wrap", None)

    @wrap.setter
    def wrap(self, v):
        self._inner.wrap = v

    def __iter__(self):
        skip, self._skip = self._skip, 0
        budget = max(self.steps_per_epoch - skip, 0)
        it = iter(self._inner)
        used = 0
        for batch in it:
            if used >= budget:
                close = getattr(it, "close", None)
                if close:
                    close()
                break
            # a group-wrap item ("group", n, data) advances the step
            # budget by n — --num-steps counts steps, not dispatches
            used += (batch[1] if isinstance(batch, tuple)
                     and len(batch) == 3 else 1)
            yield batch


def load_eval_params(args, cfg: Config, model):
    """Load a saved checkpoint for inference (de-normalized params — see
    train/checkpoint.py contract)."""
    from mx_rcnn_tpu.train.checkpoint import CheckpointManager

    mgr = CheckpointManager(args.prefix)
    params, _, _ = mgr.load_epoch(args.epoch, cfg, for_training=False)
    return params


def eval_params_from_args(args, cfg: Config, model):
    """Inference params for drivers that also run checkpoint-free
    (serve.py smoke/CI): under ``--synthetic`` random-init params pushed
    through the same de-normalize-at-save fold a real checkpoint carries
    (the bench ``build_infer`` recipe — plumbing and layouts are real,
    detections are noise); otherwise the checkpoint at
    ``--prefix``/``--epoch``."""
    if getattr(args, "synthetic", False):
        from mx_rcnn_tpu.train.checkpoint import denormalize_for_save

        params = init_params(model, cfg, jax.random.PRNGKey(0), batch_size=1)
        return denormalize_for_save(params, cfg)
    return load_eval_params(args, cfg, model)


def _calibration_images(args, cfg: Config, n: int) -> list:
    """Raw uint8 HWC images for the activation-calibration shard: the
    TAIL of the eval image set (held out from nothing the calibration
    could overfit — scales are absmax statistics, not weights), or
    deterministic noise frames under ``--synthetic``."""
    if getattr(args, "synthetic", False):
        rng = np.random.RandomState(0)
        h, w = cfg.tpu.SCALES[0]
        return [rng.randint(0, 256, size=(h, w, 3), dtype=np.uint8)
                for _ in range(n)]
    import cv2

    imdb = get_imdb(args, cfg, test=True)
    roidb = imdb.gt_roidb()
    imgs = []
    for rec in roidb[-n:]:
        im = (rec["image_array"] if "image_array" in rec
              else cv2.imread(rec["image"], cv2.IMREAD_COLOR))
        if im is not None:
            imgs.append(np.ascontiguousarray(im))
    return imgs


def calibrate_from_args(args, cfg: Config, model, params):
    """``--calibrate-shard N`` under ``--infer-dtype int8-activation``:
    run the calibration pass (``eval.tester.calibrate_activation_scales``)
    over the held-out shard, persist the per-tensor scales next to the
    AOT marker manifest (``ProgramRegistry.save_act_scales``), and return
    them for the Predictor.  Returns ``None`` when calibration is not
    requested — the Predictor then auto-loads persisted scales for the
    same config digest, or degrades to weight-only int8 with a warning."""
    n = int(getattr(args, "calibrate_shard", 0) or 0)
    if getattr(args, "infer_dtype", "float32") != "int8-activation":
        if n > 0:
            logger.warning("--calibrate-shard only applies to "
                           "--infer-dtype int8-activation — ignored")
        return None
    if n <= 0:
        return None
    from mx_rcnn_tpu.compile import ProgramRegistry
    from mx_rcnn_tpu.eval.tester import calibrate_activation_scales

    tensors = calibrate_activation_scales(
        model, params, cfg, _calibration_images(args, cfg, n), max_images=n)
    path = ProgramRegistry(cfg, dtype="int8-activation").save_act_scales(
        tensors)
    if path:
        logger.info("persisted %d activation scale(s) to %s",
                    len(tensors), path)
    else:
        logger.warning("no program cache configured (--program-cache / "
                       "MXR_PROGRAM_CACHE) — calibrated scales apply to "
                       "this process only")
    return tensors
