"""Pipeline composer + autotuner (``train/pipeline.py``): sweep mechanics
over injected fake step functions (no model build — the real-model path
is ``python -m mx_rcnn_tpu.train.pipeline``), per-cell breakdown fields, the
sweep JSONL → telemetry-report round trip, tuned-cell persistence, and
``--tuned-pipeline`` boot precedence (explicit user flags win)."""

import argparse
import dataclasses
import json

import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.data.synthetic import SyntheticDataset
from mx_rcnn_tpu.telemetry.report import aggregate, load_events, render_table
from mx_rcnn_tpu.train.pipeline import (PipelineCell, PipelineSweep,
                                        apply_tuned_to_args, cell_config,
                                        load_tuned, parse_cells,
                                        pipeline_digest, save_tuned)


def tiny_cfg():
    cfg = generate_config(
        "resnet50", "PascalVOC",
        TRAIN__RPN_PRE_NMS_TOP_N=200, TRAIN__RPN_POST_NMS_TOP_N=32,
        TRAIN__BATCH_ROIS=16,
        tpu__SCALES=((64, 96),), tpu__MAX_GT=4,
    )
    return cfg.replace(network=dataclasses.replace(
        cfg.network, ANCHOR_SCALES=(2, 4), PIXEL_STDS=(127.0, 127.0, 127.0)))


def tiny_roidb(n=6):
    return SyntheticDataset(num_images=n, num_classes=5,
                            height=64, width=96).gt_roidb()


def fake_build():
    """Step functions with the fit dispatch contract but no model: state
    is a step counter, metrics a host scalar."""
    def steps(k):
        def step(state, batch, key):
            return state + 1, {"total_loss": np.float32(0.0)}

        def multi(state, batch, key):
            return state + k, {"total_loss": np.float32(0.0)}

        return step, (multi if k > 1 else None)

    return 0, steps


BREAKDOWN_FIELDS = ("imgs_per_sec", "loader_wait_s", "dispatch_s",
                    "fetch_stall_s", "assembly_wait_s", "loader_wait_frac",
                    "loader_wait_ok")


def test_parse_cells_k_major_product():
    cells = parse_cells([1, 2], [0, 2], [2], device_prep=(False, True))
    assert len(cells) == 8
    assert cells[0] == PipelineCell(1, 0, 2, False)
    assert cells[1] == PipelineCell(1, 0, 2, True)
    assert cells[-1] == PipelineCell(2, 2, 2, True)
    assert cells[0].label == "k1_w0_p2"
    assert cells[1].label == "k1_w0_p2_dp"


def test_sweep_breakdown_and_jsonl_roundtrip(tmp_path, monkeypatch):
    """Every cell reports the full wait breakdown; the sweep JSONL is
    telemetry-meta-shaped and folds into the report's pipeline table."""
    monkeypatch.setenv("MXR_PROGRAM_CACHE", str(tmp_path))
    sweep = PipelineSweep(tiny_cfg(), tiny_roidb(), batch=2,
                          build_steps=fake_build)
    cells = parse_cells([1, 2], [0], [2])
    out_jsonl = str(tmp_path / "sweep.jsonl")
    res = sweep.sweep(cells, epochs=1, warmup_epochs=1,
                      sweep_jsonl=out_jsonl)
    assert len(res["cells"]) == 2
    for row in res["cells"]:
        for f in BREAKDOWN_FIELDS:
            assert f in row, f
        assert row["steps"] * 2 == row["imgs"]
    assert res["best"] == max(res["cells"],
                              key=lambda r: r["imgs_per_sec"])
    # a fake-step sweep is never loader-bound in dispatch terms, but the
    # tripwire fields must be present and consistent either way
    for row in res["cells"]:
        assert row["loader_wait_ok"] == (row["loader_wait_frac"] <= 0.10)

    summary = aggregate(load_events([out_jsonl]))
    assert [r["cell"] for r in summary["pipeline"]] == \
        [r["cell"] for r in res["cells"]]
    table = render_table(summary)
    assert "pipeline cell" in table
    for row in res["cells"]:
        assert row["cell"] in table


def test_group_cells_count_all_steps(tmp_path, monkeypatch):
    """k>1 cells go through the tagged group wrap: the per-cell step count
    must equal the roidb coverage (groups counted by n, remainder as
    singles), not the dispatch count."""
    monkeypatch.setenv("MXR_PROGRAM_CACHE", str(tmp_path))
    sweep = PipelineSweep(tiny_cfg(), tiny_roidb(6), batch=1,
                          build_steps=fake_build)
    res = sweep.run_cell(PipelineCell(k=4, workers=0, prefetch=2), epochs=1)
    assert res["steps"] == 6
    assert res["imgs"] == 6


def test_auto_tune_persist_and_load(tmp_path, monkeypatch):
    monkeypatch.setenv("MXR_PROGRAM_CACHE", str(tmp_path))
    cfg = tiny_cfg()
    sweep = PipelineSweep(cfg, tiny_roidb(), batch=1,
                          build_steps=fake_build)
    res = sweep.sweep(parse_cells([1], [0], [2, 4]), auto_tune=True)
    assert res["tuned_file"] == str(tmp_path / "pipeline_tuned.json")
    tuned = load_tuned(cfg)
    assert tuned is not None
    best = res["best"]
    assert (tuned["k"], tuned["workers"], tuned["prefetch"]) == \
        (best["k"], best["workers"], best["prefetch"])
    with open(res["tuned_file"]) as f:
        doc = json.load(f)
    assert doc["schema"] == "mxr-pipeline-tuned-v1"
    assert pipeline_digest(cfg) in doc["tuned"]


def test_digest_invariant_under_tuned_fields():
    """Applying a tuned cell to the config must not change the lookup key
    — otherwise a tuned boot could never find its own tuning."""
    cfg = tiny_cfg()
    cell = PipelineCell(k=4, workers=2, prefetch=6, device_prep=True)
    assert pipeline_digest(cfg) == pipeline_digest(cell_config(cfg, cell))


def boot_args(**kw):
    defaults = dict(loader_workers=None, prefetch=None, device_prep=False,
                    steps_per_dispatch=1)
    defaults.update(kw)
    return argparse.Namespace(**defaults)


def test_apply_tuned_all_defaults(tmp_path):
    cfg = tiny_cfg()
    path = str(tmp_path / "pipeline_tuned.json")
    save_tuned(cfg, PipelineCell(4, 2, 6, True),
               {"imgs_per_sec": 10.0, "loader_wait_frac": 0.01}, path=path)
    args = boot_args()
    out = apply_tuned_to_args(args, cfg, path=path)
    assert args.steps_per_dispatch == 4
    assert out.tpu.LOADER_WORKERS == 2
    assert out.tpu.PREFETCH == 6
    assert out.tpu.DEVICE_PREP is True


def test_apply_tuned_user_flags_win(tmp_path):
    """Per-field precedence: only fields left at parser defaults are
    overridden by the persisted cell."""
    cfg = tiny_cfg().replace(tpu=dataclasses.replace(
        tiny_cfg().tpu, LOADER_WORKERS=1))
    path = str(tmp_path / "pipeline_tuned.json")
    save_tuned(cfg, PipelineCell(4, 2, 6, True),
               {"imgs_per_sec": 10.0, "loader_wait_frac": 0.01}, path=path)
    args = boot_args(loader_workers=1, steps_per_dispatch=2)
    out = apply_tuned_to_args(args, cfg, path=path)
    assert args.steps_per_dispatch == 2          # user's k kept
    assert out.tpu.LOADER_WORKERS == 1           # user's workers kept
    assert out.tpu.PREFETCH == 6                 # tuned applied
    assert out.tpu.DEVICE_PREP is True           # tuned applied


def test_apply_tuned_missing_is_soft(tmp_path):
    cfg = tiny_cfg()
    args = boot_args()
    out = apply_tuned_to_args(args, cfg,
                              path=str(tmp_path / "nope.json"))
    assert out == cfg
    assert args.steps_per_dispatch == 1
