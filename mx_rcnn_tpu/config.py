"""Configuration system.

The reference keeps a global mutable ``easydict`` tree (``rcnn/config.py``:
``config``, ``default``, ``generate_config(network, dataset)``) that every
layer reads.  Field names and default values below deliberately preserve the
reference's, so a user of the reference can audit them one-to-one — but the
container is a frozen dataclass tree: immutable, hashable (so it can be a
static argument to ``jax.jit``), and assembled by a pure ``generate_config``
instead of in-place mutation.

Reference parity notes
----------------------
* ``TrainConfig`` mirrors ``config.TRAIN.*`` (BATCH_ROIS=128,
  FG_FRACTION=0.25, RPN_* anchor/NMS params, bbox normalization
  means/stds, END2END flag).
* ``TestConfig`` mirrors ``config.TEST.*`` (RPN_PRE/POST_NMS_TOP_N,
  NMS=0.3, max_per_image).
* ``generate_config(network, dataset)`` applies the network/dataset preset
  dicts exactly like the reference's, returning a new frozen config.
* TPU-specific additions are grouped in their own fields and documented as
  such (scale buckets replacing ``MutableModule`` rebinding, MAX_GT padding,
  mesh axes) — they are additive, not renames.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Tuple


@dataclass(frozen=True)
class TrainConfig:
    """Mirrors reference ``config.TRAIN``."""

    # whether to train RPN+RCNN jointly (train_end2end.py) or staged
    END2END: bool = True
    # scale-jitter: pick a random scale index per image (reference: single scale)
    SHUFFLE: bool = True
    FLIP: bool = True

    # images per device-step (reference: per-GPU batch from --ctx split)
    BATCH_IMAGES: int = 1
    # R-CNN sampled RoIs per image
    BATCH_ROIS: int = 128
    FG_FRACTION: float = 0.25
    FG_THRESH: float = 0.5
    BG_THRESH_HI: float = 0.5
    BG_THRESH_LO: float = 0.0

    # bbox regression target normalization (folded into weights at save time,
    # see train/checkpoint.py — same contract as reference do_checkpoint)
    BBOX_NORMALIZATION_PRECOMPUTED: bool = True
    BBOX_MEANS: Tuple[float, float, float, float] = (0.0, 0.0, 0.0, 0.0)
    BBOX_STDS: Tuple[float, float, float, float] = (0.1, 0.1, 0.2, 0.2)

    # RPN anchor target assignment
    RPN_FG_FRACTION: float = 0.5
    RPN_BATCH_SIZE: int = 256
    RPN_POSITIVE_OVERLAP: float = 0.7
    RPN_NEGATIVE_OVERLAP: float = 0.3
    RPN_CLOBBER_POSITIVES: bool = False
    RPN_ALLOWED_BORDER: int = 0
    # Opt-in: store the (N, G) anchor-IoU matrix in bf16 before its three
    # reduction passes (max/argmax per anchor, max per gt), halving the HBM
    # traffic that dominates assign cost at FPN's 155 520 anchors.  IoU is
    # still COMPUTED in f32 (the cast fuses into the producer); only the
    # stored matrix and the 0.7/0.3 threshold comparisons round to bf16
    # (~3 decimal digits → marginal anchors near the thresholds may flip
    # label, a statistical not systematic change).  Divergence-ledger
    # treatment (BASELINE.md): default OFF = exact reference semantics.
    RPN_ASSIGN_IOU_BF16: bool = False

    # RPN proposal generation (training-time Proposal op params)
    CXX_PROPOSAL: bool = True  # reference flag name; here: use Pallas kernel
    RPN_NMS_THRESH: float = 0.7
    RPN_PRE_NMS_TOP_N: int = 12000
    RPN_POST_NMS_TOP_N: int = 2000
    RPN_MIN_SIZE: int = 16

    # optimizer (reference train_end2end defaults)
    LR: float = 0.001
    LR_STEP: Tuple[int, ...] = (7,)  # epochs at which lr decays 10x
    LR_FACTOR: float = 0.1
    MOMENTUM: float = 0.9
    WD: float = 0.0005
    CLIP_GRADIENT: float = 5.0
    # momentum-accumulator storage dtype ("float32" | "bfloat16").  The
    # update is HBM-bandwidth-bound (every buffer read+written once per
    # step); bf16 storage halves the momentum traffic (measured −0.26 ms
    # device on the classic step).  Update math stays f32 (the trace is
    # upcast before g + mu*t), params stay f32 master weights — only the
    # stored trace rounds.  Default is "float32" — exact reference (MXNet
    # SGD) momentum semantics; the mini-VOC fixture A/B measured bf16
    # neutral (BASELINE.md round-3 divergence ledger) but fixture
    # neutrality cannot bound a VOC07/COCO regression.  The SPEED half of
    # the claim is undecided — ROADMAP D7: one A/B on a train cell of the
    # benchmark, then this becomes the only path or goes (the CPU A/B
    # that ``bench.py`` ran was deleted with it in PR 30) — so bf16 stays
    # opt-in until that A/B on real TPU hardware plus a real-dataset
    # accuracy run pins (or retires) the −0.26 ms figure.
    OPT_ACC_DTYPE: str = "float32"
    WARMUP: bool = False
    WARMUP_LR: float = 0.0
    WARMUP_STEP: int = 0

    # Mask R-CNN
    MASK_SIZE: int = 28


@dataclass(frozen=True)
class TestConfig:
    """Mirrors reference ``config.TEST``."""

    HAS_RPN: bool = True
    BATCH_IMAGES: int = 1
    CXX_PROPOSAL: bool = True
    RPN_NMS_THRESH: float = 0.7
    RPN_PRE_NMS_TOP_N: int = 6000
    RPN_POST_NMS_TOP_N: int = 300
    RPN_MIN_SIZE: int = 16
    # final per-class detection NMS
    NMS: float = 0.3
    # score threshold applied in pred_eval
    THRESH: float = 1e-3
    MAX_PER_IMAGE: int = 100
    # proposal-file path mode for alternate training (ROIIter)
    PROPOSAL: str = "rpn"
    # mask eval paste+RLE strategy (all three agree to ulp-at-threshold;
    # host costs measured in round 4 at the 100-det worst case; which
    # mode wins on a locally attached chip is ROADMAP S5's to measure):
    #   "native": ship (R,28,28) probabilities (~313 KB/img), fused C++
    #       separable paste+RLE (no full-frame materialization) — host
    #       ~10-25 ms/img, smallest transfer; the default.
    #   "device": MXU separable paste + bit-pack on chip, ONE packed
    #       bitplane readback (~6.6 MB/img) + C++ RLE — host ~8 ms/img;
    #       wins when the chip-host link is fast and the host is weak.
    #   "host": the reference's per-detection cv2 paste (~150 ms/img) —
    #       the behavioral oracle and the no-native-lib fallback.
    MASK_PASTE: str = "native"


@dataclass(frozen=True)
class NetworkConfig:
    """Mirrors the reference's per-network preset dict
    (``config.py: network.vgg / network.resnet``)."""

    NETWORK: str = "resnet50"
    # ImageNet pretrained checkpoint (converted .npz; see utils/load_model.py)
    PRETRAINED: str = "model/pretrained"
    PRETRAINED_EPOCH: int = 0
    PIXEL_MEANS: Tuple[float, float, float] = (123.68, 116.779, 103.939)
    PIXEL_STDS: Tuple[float, float, float] = (1.0, 1.0, 1.0)
    IMAGE_STRIDE: int = 32
    RPN_FEAT_STRIDE: int = 16
    RCNN_FEAT_STRIDE: int = 16
    FIXED_PARAMS: Tuple[str, ...] = ("conv1", "bn1", "stage1", "gamma", "beta")
    FIXED_PARAMS_SHARED: Tuple[str, ...] = ("conv1", "bn1", "stage1", "stage2", "stage3", "gamma", "beta")
    ANCHOR_SCALES: Tuple[int, ...] = (8, 16, 32)
    ANCHOR_RATIOS: Tuple[float, ...] = (0.5, 1.0, 2.0)
    # FPN (capability target per BASELINE.json configs 4-5; not in classic ref)
    HAS_FPN: bool = False
    # host-side 2x2 space-to-depth: the loader ships images as
    # (H/2, W/2, 12) so the stem's s2d regroup costs zero device time
    # (~1 ms/step of lane-hostile transposes otherwise); ResNet stems only
    HOST_S2D: bool = False
    # host-side row flattening: the loader ships images as (H, W·3) — a
    # view, no copy — so the batch meets the device with a lane-dense
    # minor dimension.  A (…, W, 3) float32 array is laid out on the chip
    # with its 3 channels padded to a 128-lane tile, and the host half of
    # that transfer took 0.5 s a batch of 8 × 1024 × 1024 (PR 34, on the
    # chip); a ViT patch embedding regroups rows into patches on the
    # device either way (models/vit.py PatchEmbed takes both forms)
    HOST_ROWS: bool = False
    FPN_FEAT_STRIDES: Tuple[int, ...] = (4, 8, 16, 32, 64)
    FPN_ANCHOR_SCALES: Tuple[int, ...] = (8,)
    FPN_OUT_CHANNELS: int = 256
    HAS_MASK: bool = False
    # plain-ViT trunk (NETWORK="vit": models/vit.py, ViTDet): ViT-B widths.
    # Blocks not named in VIT_GLOBAL_BLOCKS attend inside VIT_WINDOW-sided
    # windows of the token grid.  The position vectors are held at the
    # grid of tpu.SCALES[0], which has to be square.
    VIT_PATCH: int = 16
    VIT_WIDTH: int = 768
    VIT_DEPTH: int = 12
    VIT_HEADS: int = 12
    VIT_WINDOW: int = 14
    VIT_GLOBAL_BLOCKS: Tuple[int, ...] = (2, 5, 8, 11)
    # Cascade R-CNN (arXiv:1906.09756): a box head a stage, each pooling
    # again at the boxes the one before decoded and clipped (models/fpn.py
    # roi_cascade), one box a RoI for every class, the scores averaged over
    # the stages.  Stage t trains at foreground IoU ROI_CASCADE_FG_THRESH
    # [t - 1] against targets normalised by 1 / ROI_CASCADE_WEIGHTS[t - 1]
    # (Detectron2's cascade_bbox_reg_weights); train/checkpoint.py folds
    # them into each stage's own output layer at save, so the served
    # layers predict raw deltas.  Empty: one box head, run once
    ROI_CASCADE_WEIGHTS: Tuple[Tuple[float, ...], ...] = ()
    ROI_CASCADE_FG_THRESH: Tuple[float, ...] = ()

    @property
    def ROI_CASCADE_STAGES(self) -> int:
        """Box stages — derived from the stages' weights, never stored; 1 =
        one box head with per-class boxes, more = a RoI cascade with one
        class-agnostic box a RoI."""
        return max(len(self.ROI_CASCADE_WEIGHTS), 1)

    @property
    def NUM_ANCHORS(self) -> int:
        """Anchors per feature cell — derived, never stored, so it cannot
        drift from the scale/ratio tuples (FPN levels use one scale each)."""
        scales = self.FPN_ANCHOR_SCALES if self.HAS_FPN else self.ANCHOR_SCALES
        return len(self.ANCHOR_RATIOS) * len(scales)


@dataclass(frozen=True)
class DatasetConfig:
    """Mirrors the reference's per-dataset preset dict."""

    DATASET: str = "PascalVOC"
    IMAGE_SET: str = "2007_trainval"
    TEST_IMAGE_SET: str = "2007_test"
    ROOT_PATH: str = "data"
    DATASET_PATH: str = "data/VOCdevkit"
    NUM_CLASSES: int = 21  # includes __background__


@dataclass(frozen=True)
class TPUConfig:
    """TPU-native additions (no reference counterpart; documented divergence).

    The reference handles variable image sizes by rebinding executors
    (``rcnn/core/module.py: MutableModule``).  Under XLA we instead bucket
    images into a small set of static padded shapes; each bucket has one
    compiled program.
    """

    # (short_side, long_side) scale buckets; first is the reference SCALES[0]
    SCALES: Tuple[Tuple[int, int], ...] = ((600, 1000),)
    # padded max gt boxes per image
    MAX_GT: int = 100
    # data-parallel mesh axis name and DCN axis for multi-slice
    MESH_AXIS_DATA: str = "data"
    MESH_AXIS_MODEL: str = "model"
    # compute dtype for the backbone (params stay f32)
    COMPUTE_DTYPE: str = "bfloat16"
    # fused Pallas assign-IoU reductions (kernels/assign_pallas.py): the
    # (N, G) anchor-IoU matrix never materializes — IoU is recomputed per
    # tile on the fly (ULP-level f32 parity; ~100x less HBM traffic at
    # FPN's 155k anchors).  Auto-falls-back off-TPU and when MAX_GT > 128.
    # MEASURED AND REJECTED as the default (round 4, on-chip).  The gate
    # is green (check_pallas.py equivalence OK on TPU v5 lite) but the
    # kernel LOSES on device time: xplane-profiled FPN step 23.15 ms
    # fused vs 21.95 ms dense (r4_tpu_session3.log), matching the chained
    # standalone microbench (4.69 vs 2.75 ms @116736x100).  Wall-clock
    # train A/Bs that showed fused ahead (41.07 vs 38.33 imgs/s) did not
    # survive an interleaved repeat (39.15 vs 39.07) — host-clock noise
    # around the dispatches, which is why the device profile is the
    # deciding instrument.
    # The recompute-per-tile traffic saving is real but the recompute
    # cost exceeds it at G=100; stays available as an opt-in and as a
    # libtpu-upgrade retry candidate.
    ASSIGN_FUSED: bool = False
    # ROIAlign samples per bin axis.  Classic configs default to 1: still
    # at-or-above the reference's integer-binned ROIPooling fidelity and
    # 1.8x faster end-to-end (4x fewer gather points).  FPN/Mask presets
    # get 2 via generate_config — Mask R-CNN paper parity for the mask head.
    # NOTE: affects numerics; train and eval must use the same value (any
    # consistent generate_config call does).
    ROI_SAMPLING_RATIO: int = 1
    # RoI pooling reduction: "avg" (ROIAlign paper / torchvision), "max"
    # (max over the same continuous sample grid), or "exact" — the
    # reference's integer-binned CUDA ROIPooling semantics
    # (rounded corners, overlapping integer bins, plain max, empty-bin
    # zeros; ops/roi_align.py:_roi_pool_exact).  "exact" is the transplant
    # mode: inference on MXNet-trained weights reproduces the op those
    # weights saw.  "avg"/"max" are identical at ROI_SAMPLING_RATIO=1;
    # the A/B ledger in BASELINE.md measures the deltas.
    ROI_MODE: str = "avg"
    # host→device prefetch depth
    PREFETCH: int = 2
    # overlapped eval (eval/pipeline.py): max batches dispatched-but-not-
    # post-processed; 2 = double-buffering (forward N+1 overlaps host
    # post-process N); 0 via --eval-inflight falls back to the serial
    # reference loop
    EVAL_INFLIGHT: int = 2
    # width of the eval host post-process thread pool (decode + per-class
    # NMS + mask paste); results are index-addressed so width never
    # changes the output
    EVAL_HOST_WORKERS: int = 2
    # consumer-side watchdog on the prefetch queue: no producer heartbeat
    # for this long raises a diagnostic naming the producer state instead
    # of the training loop hanging forever on a stuck filesystem read
    # (<= 0 disables)
    PREFETCH_WATCHDOG_S: float = 600.0
    # host input pipeline worker processes (data/workers.py): 0 (default)
    # keeps the single-thread producer, bit-identical to before the pool
    # existed; N > 0 fans the per-sample decode/resize/flip hot path over
    # N processes with shared-memory handover — same batches, same order,
    # any seed (the epoch plan is drawn once on the consumer and sharded
    # by index)
    LOADER_WORKERS: int = 0
    # rematerialize the backbone stages in the backward pass
    # (nn.remat on each ResNetStage): trades recompute FLOPs for HBM
    # traffic — the B>=16 lever for the measured relu-backward
    # compare_select slowdown once per-tensor working sets pass ~40 MB
    # (BASELINE.md batch-scaling table).  Param tree and numerics are
    # unchanged; off by default pending the on-chip A/B.
    REMAT_BACKBONE: bool = False
    # device-side preprocessing (data/device_prep.py): loaders emit raw
    # bucket-staged uint8 pixels and a jitted per-bucket program does
    # resize/flip/normalize/pad (and HOST_S2D) on device, overlapped with
    # the step via the prefetch thread.  Off (default) keeps the host
    # numpy path bit-identical to before the feature existed.  Train
    # loaders honor it directly; eval opts in per TestLoader
    # (test.py --device-prep → Predictor.batch_put preps on device); the
    # serve engine's fused equivalent is --serve-e2e.  Mesh plans raise —
    # host prep only there.
    DEVICE_PREP: bool = False
    # output dtype of the device preprocess program ("float32" or
    # "bfloat16") — the host path is float32-only
    DEVICE_PREP_DTYPE: str = "float32"


@dataclass(frozen=True)
class Config:
    """Root config. Frozen + hashable → usable as a jit static arg."""

    network: NetworkConfig = field(default_factory=NetworkConfig)
    dataset: DatasetConfig = field(default_factory=DatasetConfig)
    TRAIN: TrainConfig = field(default_factory=TrainConfig)
    TEST: TestConfig = field(default_factory=TestConfig)
    tpu: TPUConfig = field(default_factory=TPUConfig)

    @property
    def NUM_CLASSES(self) -> int:
        return self.dataset.NUM_CLASSES

    def replace(self, **kw) -> "Config":
        return replace(self, **kw)


# ---------------------------------------------------------------------------
# Preset registry — the analogue of the reference's `network` / `dataset`
# easydict preset blocks applied by generate_config().
# ---------------------------------------------------------------------------

_NETWORK_PRESETS = {
    "vgg16": dict(
        NETWORK="vgg16",
        IMAGE_STRIDE=0,
        RPN_FEAT_STRIDE=16,
        RCNN_FEAT_STRIDE=16,
        FIXED_PARAMS=("conv1", "conv2"),
        FIXED_PARAMS_SHARED=("conv1", "conv2", "conv3", "conv4", "conv5"),
        HAS_FPN=False,
    ),
    # classic resnet presets are generated below — one dict per depth,
    # identical apart from NETWORK (single source of truth)
    # FPN shared trunk = backbone stages 1-4 + the neck (lateral*/post* conv
    # names), so alternate-training rounds 2 keep ALL shared features frozen
    "resnet50_fpn": dict(
        NETWORK="resnet50",
        HOST_S2D=True,
        IMAGE_STRIDE=32,
        HAS_FPN=True,
        RCNN_FEAT_STRIDE=4,
        FPN_ANCHOR_SCALES=(8,),
        FIXED_PARAMS_SHARED=("conv1", "bn1", "stage1", "stage2", "stage3",
                             "stage4", "lateral", "post", "gamma", "beta"),
    ),
    "resnet101_fpn": dict(
        NETWORK="resnet101",
        HOST_S2D=True,
        IMAGE_STRIDE=32,
        HAS_FPN=True,
        RCNN_FEAT_STRIDE=4,
        FPN_ANCHOR_SCALES=(8,),
        FIXED_PARAMS_SHARED=("conv1", "bn1", "stage1", "stage2", "stage3",
                             "stage4", "lateral", "post", "gamma", "beta"),
    ),
    "resnet101_fpn_mask": dict(
        NETWORK="resnet101",
        HOST_S2D=True,
        IMAGE_STRIDE=32,
        HAS_FPN=True,
        HAS_MASK=True,
        RCNN_FEAT_STRIDE=4,
        FPN_ANCHOR_SCALES=(8,),
        FIXED_PARAMS_SHARED=("conv1", "bn1", "stage1", "stage2", "stage3",
                             "stage4", "lateral", "post", "gamma", "beta"),
    ),
    # Cascade Mask R-CNN (Cai & Vasconcelos, arXiv:1906.09756; Detectron2
    # CascadeROIHeads): resnet101_fpn_mask's trunk, pyramid, RPN, stage-1
    # head and mask head, a 4-wide class-agnostic box output on each of
    # three box heads; stages 2-3 run in a program of their own
    "resnet101_fpn_cascade_mask": dict(
        NETWORK="resnet101",
        HOST_S2D=True,
        IMAGE_STRIDE=32,
        HAS_FPN=True,
        HAS_MASK=True,
        RCNN_FEAT_STRIDE=4,
        FPN_ANCHOR_SCALES=(8,),
        FIXED_PARAMS_SHARED=("conv1", "bn1", "stage1", "stage2", "stage3",
                             "stage4", "lateral", "post", "gamma", "beta"),
        ROI_CASCADE_WEIGHTS=((10.0, 10.0, 5.0, 5.0), (20.0, 20.0, 10.0, 10.0),
                             (30.0, 30.0, 15.0, 15.0)),
        ROI_CASCADE_FG_THRESH=(0.5, 0.6, 0.7),
    ),
    # Mask R-CNN on a plain ViT-B trunk with the simple feature pyramid
    # (ViTDet, arXiv:2203.16527): one square bucket (generate_config sets
    # 1024 x 1024), a 2-conv RPN, a 4-conv + 1-FC box head and LayerNorm in
    # the heads.  Nothing is frozen: the trunk trains whole.
    "vitdet_b_mask": dict(
        NETWORK="vit",
        HOST_ROWS=True,
        IMAGE_STRIDE=32,
        HAS_FPN=True,
        HAS_MASK=True,
        RCNN_FEAT_STRIDE=4,
        FPN_ANCHOR_SCALES=(8,),
        FIXED_PARAMS=(),
        FIXED_PARAMS_SHARED=("backbone", "neck"),
    ),
}

for _depth in ("resnet50", "resnet101", "resnet152"):
    _NETWORK_PRESETS[_depth] = dict(
        NETWORK=_depth,
        HOST_S2D=True,
        IMAGE_STRIDE=32,
        FIXED_PARAMS=("conv1", "bn1", "stage1", "gamma", "beta"),
        FIXED_PARAMS_SHARED=("conv1", "bn1", "stage1", "stage2", "stage3",
                             "gamma", "beta"),
    )

_DATASET_PRESETS = {
    "PascalVOC": dict(
        DATASET="PascalVOC",
        IMAGE_SET="2007_trainval",
        TEST_IMAGE_SET="2007_test",
        ROOT_PATH="data",
        DATASET_PATH="data/VOCdevkit",
        NUM_CLASSES=21,
    ),
    "PascalVOC0712": dict(
        DATASET="PascalVOC",
        IMAGE_SET="2007_trainval+2012_trainval",
        TEST_IMAGE_SET="2007_test",
        ROOT_PATH="data",
        DATASET_PATH="data/VOCdevkit",
        NUM_CLASSES=21,
    ),
    "coco": dict(
        DATASET="coco",
        IMAGE_SET="train2017",
        TEST_IMAGE_SET="val2017",
        ROOT_PATH="data",
        DATASET_PATH="data/coco",
        NUM_CLASSES=81,
    ),
}


def generate_config(network: str, dataset: str, **overrides) -> Config:
    """Build a frozen Config from network+dataset preset names.

    Same role as the reference's ``generate_config`` (rcnn/config.py), which
    mutates the global ``config``/``default`` easydicts in place; here it
    returns a fresh immutable tree.

    ``overrides`` may address nested fields with double-underscore paths,
    e.g. ``generate_config('resnet50', 'PascalVOC', TRAIN__BATCH_IMAGES=2)``.
    """
    if network not in _NETWORK_PRESETS:
        raise KeyError(f"unknown network '{network}'; have {sorted(_NETWORK_PRESETS)}")
    if dataset not in _DATASET_PRESETS:
        raise KeyError(f"unknown dataset '{dataset}'; have {sorted(_DATASET_PRESETS)}")

    net = NetworkConfig(**_NETWORK_PRESETS[network])
    ds = DatasetConfig(**_DATASET_PRESETS[dataset])
    train = TrainConfig()
    test = TestConfig()
    tpu = TPUConfig()

    # COCO schedules differ from VOC in the reference scripts
    if dataset == "coco":
        train = replace(train, LR_STEP=(6,), BATCH_ROIS=128)
        tpu = replace(tpu, SCALES=((800, 1333),))

    # FPN/Mask configs keep the Mask R-CNN paper's 2-sample ROIAlign
    if net.HAS_FPN:
        tpu = replace(tpu, ROI_SAMPLING_RATIO=2)
    # a ViT trunk holds its position vectors at one square grid
    if net.NETWORK == "vit":
        tpu = replace(tpu, SCALES=((1024, 1024),))

    cfg = Config(network=net, dataset=ds, TRAIN=train, TEST=test, tpu=tpu)

    # apply double-underscore-path overrides
    for key, val in overrides.items():
        parts = key.split("__")
        if len(parts) == 1:
            cfg = replace(cfg, **{parts[0]: val})
        elif len(parts) == 2:
            sub = getattr(cfg, parts[0])
            cfg = replace(cfg, **{parts[0]: replace(sub, **{parts[1]: val})})
        else:
            raise KeyError(f"override path too deep: {key}")
    return cfg


def list_networks():
    return sorted(_NETWORK_PRESETS)


def list_datasets():
    return sorted(_DATASET_PRESETS)


def config_to_dict(cfg: Config) -> dict:
    """Flatten for logging/serialization."""
    return dataclasses.asdict(cfg)
