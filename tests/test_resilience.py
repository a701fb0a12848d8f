"""Fault-tolerance subsystem (train/resilience.py + its trainer/
checkpoint/loader wiring), driven by the tests/faults.py injectors:
step checkpoints + exact mid-epoch auto-resume, NaN sentinel policies,
loader fault isolation, prefetch watchdog, preemption, I/O retry."""

import dataclasses
import glob
import json
import threading

import jax
import numpy as np
import pytest

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.data import AnchorLoader, SyntheticDataset
from mx_rcnn_tpu.data.loader import _load_record_isolated, _Prefetcher
from mx_rcnn_tpu.models import build_model, init_params
from mx_rcnn_tpu.telemetry.report import (RECOVERY_COUNTERS, aggregate,
                                          load_events, render_table)
from mx_rcnn_tpu.train import NonFiniteLossError, ResilienceOptions, fit
from mx_rcnn_tpu.train.checkpoint import CheckpointManager
from mx_rcnn_tpu.train.resilience import (decode_step_key, encode_step_key,
                                          retry_io)

from .faults import (NanBatchLoader, SignalAtBatchLoader, corrupt_record,
                     flaky_saves, hang_until)


def tiny_cfg():
    # test_fit_resume's config, verbatim — the persistent compile cache
    # makes every fit() here reuse its compiled step programs
    cfg = generate_config(
        "resnet50", "PascalVOC",
        TRAIN__RPN_PRE_NMS_TOP_N=200, TRAIN__RPN_POST_NMS_TOP_N=32,
        TRAIN__BATCH_ROIS=16,
    )
    net = dataclasses.replace(cfg.network, ANCHOR_SCALES=(2, 4),
                              PIXEL_STDS=(127.0, 127.0, 127.0))
    tpu = dataclasses.replace(cfg.tpu, SCALES=((64, 96),), MAX_GT=4)
    return cfg.replace(network=net, tpu=tpu)


def tiny_data(n_images=8, seed=0, shuffle=False, cfg=None):
    cfg = cfg or tiny_cfg()
    ds = SyntheticDataset(num_images=n_images, num_classes=cfg.NUM_CLASSES,
                          height=64, width=96)
    roidb = ds.gt_roidb()
    loader = AnchorLoader(roidb, cfg, batch_size=2, shuffle=shuffle,
                          seed=seed)
    return cfg, roidb, loader


def tiny_model(cfg):
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0), 1, (64, 96))
    return model, params


def leaves(params):
    return [np.asarray(jax.device_get(x))
            for x in jax.tree_util.tree_leaves(params)]


# -- unit level ------------------------------------------------------------


def test_step_key_roundtrip():
    assert decode_step_key(encode_step_key(3, 1234)) == (3, 1234)
    assert decode_step_key(encode_step_key(0, 0)) == (0, 0)
    with pytest.raises(ValueError):
        encode_step_key(1, 10 ** 7)  # an epoch can't run that many batches


def test_resilience_options_validation():
    with pytest.raises(ValueError):
        ResilienceOptions(nan_policy="explode")
    with pytest.raises(ValueError):
        ResilienceOptions(save_every_n_steps=-1)
    assert not ResilienceOptions().enabled
    ropt = ResilienceOptions(nan_policy="skip")
    assert ropt.enabled and ropt.sentinel and ropt.skip_nonfinite
    # from_args tolerates namespaces without the flags (alternate stages)
    assert not ResilienceOptions.from_args(object()).enabled


def test_retry_io_backoff_and_exhaustion():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert retry_io(flaky, what="t", retries=3, backoff_s=0.001) == "ok"
    assert calls["n"] == 3
    with pytest.raises(OSError):
        retry_io(lambda: (_ for _ in ()).throw(OSError("always")),
                 what="t", retries=1, backoff_s=0.001)
    with pytest.raises(KeyError):  # programming errors are NOT retried
        retry_io(lambda: {}["x"], what="t", retries=3, backoff_s=0.001)


def test_load_epoch_missing_lists_present(tmp_path):
    cfg = tiny_cfg()
    mgr = CheckpointManager(str(tmp_path / "ck"))
    with pytest.raises(FileNotFoundError, match=r"epochs present: none"):
        mgr.load_epoch(5, cfg)
    mgr.save_epoch(1, {"w": np.ones(2, np.float32)}, cfg)
    with pytest.raises(FileNotFoundError, match=r"epochs present: \[1\]"):
        mgr.load_epoch(5, cfg)


def test_step_checkpoint_roundtrip_and_resume_point(tmp_path):
    cfg = tiny_cfg()
    mgr = CheckpointManager(str(tmp_path / "ck"), step_keep=2)
    assert mgr.latest_resume_point() is None
    params = {"w": np.arange(4, dtype=np.float32)}
    key = np.asarray(jax.random.PRNGKey(7))
    mgr.save_step(1, 5, params, cfg, opt_state={"m": np.ones(4, np.float32)},
                  step=9, rng_key=key)
    assert mgr.latest_step_checkpoint() == (1, 5)
    out = mgr.load_step_checkpoint(1, 5)
    np.testing.assert_array_equal(out["params"]["w"], params["w"])
    np.testing.assert_array_equal(out["rng_key"], key)
    assert (out["step"], out["epoch"], out["consumed"]) == (9, 1, 5)
    with pytest.raises(FileNotFoundError, match="present"):
        mgr.load_step_checkpoint(2, 2)
    # a finished epoch beats its own mid-epoch saves; a newer step wins
    mgr.save_epoch(2, params, cfg)
    assert mgr.latest_resume_point() == ("epoch", 2, 0)
    mgr.save_step(2, 3, params, cfg)
    assert mgr.latest_resume_point() == ("step", 2, 3)
    # rolling window: a third step save evicts the oldest (step_keep=2)
    mgr.save_step(2, 6, params, cfg)
    assert mgr.latest_step_checkpoint() == (2, 6)
    with pytest.raises(FileNotFoundError):
        mgr.load_step_checkpoint(1, 5)


# -- loader fault isolation + watchdog + close -----------------------------


def test_bad_record_substituted_and_counted(tmp_path):
    cfg, _, loader = tiny_data(n_images=8)
    corrupt_record(loader.roidb, 2)
    telemetry.configure(str(tmp_path), rank=0, world=1)
    try:
        batches = list(loader)
    finally:
        telemetry.shutdown()
    assert len(batches) == loader.steps_per_epoch
    for b in batches:
        assert np.isfinite(b["images"]).all()
    summary = aggregate(load_events([str(tmp_path)]))
    assert summary["counters"]["loader/bad_record"] == 1
    # the recovery section of the report names it
    assert "loader/bad_record" in render_table(summary)
    assert "loader/bad_record" in RECOVERY_COUNTERS


def test_systemic_breakage_raises():
    cfg, _, loader = tiny_data(n_images=8)
    for i in range(len(loader.roidb)):
        corrupt_record(loader.roidb, i)
    with pytest.raises(RuntimeError, match="systemic"):
        list(loader)


def test_load_record_isolated_consecutive_state():
    cfg, roidb, _ = tiny_data(n_images=8)
    corrupt_record(roidb, 0)
    state = [0]
    j, sample = _load_record_isolated(roidb, 0, cfg, (64, 96), state=state)
    assert j == 1  # deterministic neighbor substitution
    assert state[0] == 0  # success resets the consecutive count
    assert sample["images"].shape[0] > 0


def test_prefetcher_close_joins_thread():
    p = _Prefetcher(iter(range(100)), depth=2)
    it = iter(p)
    assert next(it) == 0
    p.close()
    assert not p._t.is_alive()


def test_prefetcher_watchdog_diagnostic():
    release = threading.Event()
    p = _Prefetcher(hang_until(release, [1, 2]), depth=2, watchdog_s=0.3)
    try:
        assert p._get() == 1
        assert p._get() == 2
        with pytest.raises(RuntimeError, match="producer thread alive"):
            p._get()
    finally:
        release.set()
        p.close()
    assert not p._t.is_alive()


def test_epoch_plan_fast_forward_exact():
    """advance_epochs + skip_next replay the identical (indices, scale)
    tail the uninterrupted loader would have produced."""
    cfg = tiny_cfg()
    tpu = dataclasses.replace(cfg.tpu, SCALES=((64, 96), (96, 64)))
    cfg = cfg.replace(tpu=tpu)  # >1 scale: the plan draws scale RNG too
    cfg, roidb, _ = tiny_data(n_images=12, cfg=cfg)
    a = AnchorLoader(roidb, cfg, batch_size=2, shuffle=True, seed=7)
    plans = [a._take_epoch_plan() for _ in range(2)]
    b = AnchorLoader(roidb, cfg, batch_size=2, shuffle=True, seed=7)
    b.advance_epochs(1)
    b.skip_next(3)
    tail = b._take_epoch_plan()
    want = plans[1][3:]
    assert len(tail) == len(want)
    for (got_idx, got_scale), (want_idx, want_scale) in zip(tail, want):
        np.testing.assert_array_equal(got_idx, want_idx)
        assert got_scale == want_scale
    # the skip is one-shot: the next epoch is full length again
    assert len(b._take_epoch_plan()) == len(plans[0])
    b.skip_next(10 ** 6)
    with pytest.raises(ValueError, match="exceeds"):
        b._take_epoch_plan()


# -- fit()-level: sentinel policies, preemption, exact resume --------------


def test_nan_halt_dumps_and_raises(tmp_path):
    cfg, _, loader = tiny_data(n_images=8)
    model, params = tiny_model(cfg)
    prefix = str(tmp_path / "ck")
    with pytest.raises(NonFiniteLossError, match="policy=halt"):
        fit(cfg, model, params, NanBatchLoader(loader, 1),
            begin_epoch=0, end_epoch=1, prefix=prefix, frequent=1,
            resilience=ResilienceOptions(nan_policy="halt"))
    dumps = glob.glob(str(tmp_path / "ck" / "nan_dump_*.json"))
    assert dumps, "halt policy must leave a diagnostic dump"
    doc = json.load(open(dumps[0]))
    assert doc["epoch"] == 0 and "metrics" in doc


def test_nan_skip_keeps_params_finite(tmp_path):
    cfg, _, loader = tiny_data(n_images=8)
    model, params = tiny_model(cfg)
    state = fit(cfg, model, params, NanBatchLoader(loader, 1),
                begin_epoch=0, end_epoch=1, frequent=1,
                telemetry_dir=str(tmp_path / "tel"),
                resilience=ResilienceOptions(nan_policy="skip"))
    for leaf in leaves(state.params):
        assert np.isfinite(leaf).all()
    summary = json.load(open(glob.glob(str(tmp_path / "tel" /
                                           "summary*.json"))[0]))
    assert summary["counters"]["train/nan_detected"] >= 1
    assert summary["counters"]["train/nan_skipped"] >= 1


def test_nan_rollback_restores_last_good(tmp_path):
    cfg, _, loader = tiny_data(n_images=8)
    model, params = tiny_model(cfg)
    prefix = str(tmp_path / "ck")
    state = fit(cfg, model, params, NanBatchLoader(loader, 2),
                begin_epoch=0, end_epoch=1, prefix=prefix, frequent=1,
                telemetry_dir=str(tmp_path / "tel"),
                resilience=ResilienceOptions(nan_policy="rollback",
                                             save_every_n_steps=1))
    for leaf in leaves(state.params):
        assert np.isfinite(leaf).all()
    summary = json.load(open(glob.glob(str(tmp_path / "tel" /
                                           "summary*.json"))[0]))
    assert summary["counters"]["train/nan_rollback"] >= 1


def test_flaky_epoch_save_retried(tmp_path):
    cfg, _, loader = tiny_data(n_images=4)
    model, params = tiny_model(cfg)
    prefix = str(tmp_path / "ck")
    with flaky_saves(1):
        fit(cfg, model, params, loader, begin_epoch=0, end_epoch=1,
            prefix=prefix, frequent=100,
            resilience=ResilienceOptions(io_backoff_s=0.01))
    assert CheckpointManager(prefix).available_epochs() == [1]


def test_preempt_then_auto_resume_matches_uninterrupted(tmp_path):
    """The acceptance path: SIGTERM mid-epoch saves a step checkpoint and
    exits cleanly; a fresh fit with auto_resume (zero manual flags)
    fast-forwards the loader, restores params/opt/rng, and finishes with
    EXACTLY the params of a run that was never interrupted."""
    n_images, end_epoch = 8, 2
    # uninterrupted reference (auto_resume on an empty prefix = fresh
    # start — pinning that contract rides along for free)
    cfg, _, loader = tiny_data(n_images=n_images)
    model, params = tiny_model(cfg)
    ref = fit(cfg, model, params, loader, begin_epoch=0,
              end_epoch=end_epoch, prefix=str(tmp_path / "ref"), frequent=1,
              resilience=ResilienceOptions(auto_resume=True))

    # interrupted run: SIGTERM while batch 2 of epoch 0 is being pulled →
    # step checkpoint at consumed=3, clean return
    cfg2, _, loader2 = tiny_data(n_images=n_images)
    model2, params2 = tiny_model(cfg2)
    prefix = str(tmp_path / "ck")
    ropt = ResilienceOptions(auto_resume=True, save_every_n_steps=100)
    mid = fit(cfg2, model2, params2, SignalAtBatchLoader(loader2, 2),
              begin_epoch=0, end_epoch=end_epoch, prefix=prefix, frequent=1,
              resilience=ropt)
    mgr = CheckpointManager(prefix)
    assert mgr.latest_resume_point() == ("step", 0, 3)
    assert int(jax.device_get(mid.step)) == 3

    # resumed run: fresh loader (fresh RandomState — a process restart),
    # same CLI surface, auto_resume picks the step checkpoint
    cfg3, _, loader3 = tiny_data(n_images=n_images)
    model3, params3 = tiny_model(cfg3)
    out = fit(cfg3, model3, params3, loader3, begin_epoch=0,
              end_epoch=end_epoch, prefix=prefix, frequent=1,
              resilience=ropt)
    assert int(jax.device_get(out.step)) == int(jax.device_get(ref.step))
    for got, want in zip(leaves(out.params), leaves(ref.params)):
        np.testing.assert_array_equal(got, want)
    # both epochs finished after resume → epoch checkpoints exist
    assert CheckpointManager(prefix).available_epochs() == [1, 2]
