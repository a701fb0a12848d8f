"""Input-pipeline composer + autotuner: the (k steps/dispatch × N loader
workers × prefetch depth × device-prep) matrix as one driven sweep.

The primitives existed in isolation — ``--steps-per-dispatch`` chain
dispatch (trainer), the PR-4 ``data/workers.py`` shared-memory pool, the
``PREFETCH`` double-buffering queue, and now device-side preprocessing
(``data/device_prep.py``) — but their composition is what actually hides
host work, and the best cell is box- and config-dependent.  This module:

* runs each :class:`PipelineCell` through its own lean measured loop
  (NOT ``fit()``: fit builds fresh step closures per call, so a per-cell
  fit would re-compile every cell and pollute the dispatch numbers; here
  step programs are cached per k and a warmup epoch absorbs compiles),
* reports per-cell imgs/s with the PR-1 breakdown — loader_wait /
  dispatch / fetch_stall measured in-loop, assembly_wait diffed from the
  live telemetry sink,
* persists the winning cell (``--auto-tune``) to a small JSON next to
  the program cache, keyed by a tuned-field-normalized config digest, so
  ``train_end2end.py`` / ``train_alternate.py`` boot straight into the
  tuned (k, workers, prefetch, device_prep) via ``--tuned-pipeline``,
* writes ``sweep.jsonl`` — telemetry-meta-shaped ``pipeline_cell`` rows
  that ``scripts/telemetry_report.py`` folds into its pipeline table.

Entry point for humans: ``python -m mx_rcnn_tpu.train.pipeline
[--auto-tune]`` (:func:`main` below).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.compile.registry import (ENV_CACHE_BASE, ProgramRegistry,
                                          config_digest)
from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.train.trainer import LOADER_WAIT_TRIPWIRE_FRAC, \
    _make_group_wrap

TUNED_FILENAME = "pipeline_tuned.json"
TUNED_SCHEMA = "mxr-pipeline-tuned-v1"
# the command that writes the tuned file; every message that sends a user
# to the tuner names it through this
TUNE_COMMAND = "python -m mx_rcnn_tpu.train.pipeline --auto-tune"


@dataclasses.dataclass(frozen=True)
class PipelineCell:
    """One point of the tuning matrix."""

    k: int = 1            # steps per dispatch (lax.scan group size)
    workers: int = 0      # data/workers.py pool size (0 = in-thread)
    prefetch: int = 2     # host→device prefetch queue depth
    device_prep: bool = False  # data/device_prep.py on-device transform

    @property
    def label(self) -> str:
        return (f"k{self.k}_w{self.workers}_p{self.prefetch}"
                + ("_dp" if self.device_prep else ""))


def cell_config(cfg: Config, cell: PipelineCell) -> Config:
    """Fold a cell's loader-side knobs into the config (k is a fit/bench
    argument, not a config field)."""
    return cfg.replace(tpu=dataclasses.replace(
        cfg.tpu, LOADER_WORKERS=int(cell.workers),
        PREFETCH=int(cell.prefetch), DEVICE_PREP=bool(cell.device_prep)))


def pipeline_digest(cfg: Config) -> str:
    """Config digest with the TUNED fields normalized to their defaults —
    the persisted-tuning key must not change when the tuning it selects
    is applied to the config."""
    return config_digest(cfg.replace(tpu=dataclasses.replace(
        cfg.tpu, LOADER_WORKERS=0, PREFETCH=2, DEVICE_PREP=False)))


def tuned_path(base: Optional[str] = None) -> str:
    """The tuned-cell JSON lives next to the program cache (same lifecycle:
    box-local derived state, safe to delete, survives reboots)."""
    base = (base or os.environ.get(ENV_CACHE_BASE)
            or os.path.join("/tmp", "mxr_program_cache"))
    return os.path.join(base, TUNED_FILENAME)


def save_tuned(cfg: Config, cell: PipelineCell, result: dict,
               path: Optional[str] = None) -> str:
    path = path or tuned_path()
    doc = {"schema": TUNED_SCHEMA, "tuned": {}}
    try:
        with open(path) as f:
            prev = json.load(f)
        if prev.get("schema") == TUNED_SCHEMA:
            doc = prev
    except (OSError, ValueError):
        pass
    doc.setdefault("tuned", {})[pipeline_digest(cfg)] = {
        "k": int(cell.k), "workers": int(cell.workers),
        "prefetch": int(cell.prefetch),
        "device_prep": bool(cell.device_prep),
        "imgs_per_sec": float(result.get("imgs_per_sec", 0.0)),
        "loader_wait_frac": float(result.get("loader_wait_frac", 0.0)),
        "recorded_by": TUNE_COMMAND,
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def load_tuned(cfg: Config, path: Optional[str] = None) -> Optional[dict]:
    """The persisted cell for this config family, or None."""
    path = path or tuned_path()
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if doc.get("schema") != TUNED_SCHEMA:
        return None
    return doc.get("tuned", {}).get(pipeline_digest(cfg))


def _span_total(tel, name: str) -> float:
    try:
        sp = tel.summary().get("spans", {}).get(name)
        return float(sp["total_s"]) if sp else 0.0
    except Exception:
        return 0.0


class PipelineSweep:
    """Drives the matrix over one model + synthetic/real roidb.

    ``build_steps``: dependency injection for tests — a callable
    ``() -> (state, steps_factory)`` where ``steps_factory(k) ->
    (step_fn, multi_fn)`` with the fit dispatch contract
    ``fn(state, batch, key) -> (state, metrics)``.  Default builds the
    real model once and caches step programs per k, so cells differing
    only in loader knobs share every compiled program.
    """

    def __init__(self, cfg: Config, roidb: list, batch: int = 1,
                 build_steps: Optional[Callable] = None):
        self.cfg = cfg
        self.roidb = roidb
        self.batch = batch
        self.registry = ProgramRegistry(
            cfg, dtype=(cfg.tpu.COMPUTE_DTYPE if cfg.tpu.COMPUTE_DTYPE in
                        ("float32", "bfloat16") else "float32"))
        if build_steps is None:
            build_steps = self._default_build
        self._state, self._steps_factory = build_steps()
        self._steps: Dict[int, Tuple[Callable, Optional[Callable]]] = {}
        self._prep = None

    # -- model plumbing --------------------------------------------------

    def _default_build(self):
        from mx_rcnn_tpu.data.image import bucket_shape
        from mx_rcnn_tpu.models import build_model, init_params
        from mx_rcnn_tpu.train.train_step import (create_train_state,
                                                  make_multi_train_step,
                                                  make_train_step)

        cfg = self.cfg
        model = build_model(cfg)
        stride = max(cfg.network.IMAGE_STRIDE, cfg.network.RPN_FEAT_STRIDE)
        hw = bucket_shape(cfg.tpu.SCALES[0], stride, landscape=True)
        params = init_params(model, cfg, jax.random.PRNGKey(0), self.batch,
                             hw)
        state, tx, mask = create_train_state(cfg, params,
                                             steps_per_epoch=1000)

        def steps(k: int):
            step = make_train_step(model, tx, trainable_mask=mask)
            multi = (make_multi_train_step(model, tx, k,
                                           trainable_mask=mask)
                     if k > 1 else None)
            return step, multi

        return state, steps

    def _get_steps(self, k: int):
        if k not in self._steps:
            self._steps[k] = self._steps_factory(k)
        return self._steps[k]

    def _get_prep(self):
        if self._prep is None:
            from mx_rcnn_tpu.data.device_prep import DevicePrep

            dp_cfg = self.cfg.replace(tpu=dataclasses.replace(
                self.cfg.tpu, DEVICE_PREP=True))
            self._prep = DevicePrep(dp_cfg, registry=self.registry)
        return self._prep

    # -- measured loop ---------------------------------------------------

    def _dispatch(self, step_fn, multi_fn, state, item, key):
        if isinstance(item, tuple) and len(item) == 3:  # tagged group wrap
            kind, n, data = item
            fn = multi_fn if kind == "group" else step_fn
            state, metrics = fn(state, data, key)
            return state, metrics, n
        state, metrics = step_fn(state, item, key)
        return state, metrics, 1

    def run_cell(self, cell: PipelineCell, epochs: int = 1,
                 warmup_epochs: int = 1) -> dict:
        """One cell: warmup epoch(s) absorb compiles + worker spawn, then
        ``epochs`` measured epochs through the fit-identical hot loop."""
        from mx_rcnn_tpu.data.loader import AnchorLoader

        cfgc = cell_config(self.cfg, cell)
        prep = self._get_prep() if cell.device_prep else None
        step_fn, multi_fn = self._get_steps(cell.k)
        loader = AnchorLoader(self.roidb, cfgc, self.batch, shuffle=True,
                              seed=0)
        if cell.k > 1:
            loader.wrap = _make_group_wrap(cell.k, None, prep=prep)
        else:
            loader.wrap = None
            loader.put = prep.put if prep is not None else jax.device_put
        tel = telemetry.get()
        asm0 = _span_total(tel, "loader/assembly_wait")
        state = self._state
        key = jax.random.PRNGKey(0)
        metrics = None
        try:
            for _ in range(warmup_epochs):
                for item in loader:
                    key, sub = jax.random.split(key)
                    state, metrics, _n = self._dispatch(
                        step_fn, multi_fn, state, item, sub)
            if metrics is not None:
                jax.block_until_ready(metrics)

            waits = disp = 0.0
            steps = 0
            t0 = time.perf_counter()
            for _ in range(epochs):
                it = iter(loader)
                while True:
                    tw = time.perf_counter()
                    try:
                        item = next(it)
                    except StopIteration:
                        break
                    waits += time.perf_counter() - tw
                    td = time.perf_counter()
                    key, sub = jax.random.split(key)
                    state, metrics, n = self._dispatch(
                        step_fn, multi_fn, state, item, sub)
                    disp += time.perf_counter() - td
                    steps += n
            tf = time.perf_counter()
            if metrics is not None:
                jax.device_get(metrics)
            fetch = time.perf_counter() - tf
            wall = time.perf_counter() - t0
        finally:
            loader.close_workers()
        self._state = state
        asm1 = _span_total(tel, "loader/assembly_wait")
        imgs = steps * self.batch
        frac = waits / max(wall, 1e-9)
        res = {
            "cell": cell.label, "k": cell.k, "workers": cell.workers,
            "prefetch": cell.prefetch, "device_prep": cell.device_prep,
            "imgs_per_sec": round(imgs / max(wall, 1e-9), 3),
            "steps": steps, "imgs": imgs,
            "wall_s": round(wall, 4),
            "loader_wait_s": round(waits, 4),
            "dispatch_s": round(disp, 4),
            "fetch_stall_s": round(fetch, 4),
            "assembly_wait_s": round(max(asm1 - asm0, 0.0), 4),
            "loader_wait_frac": round(frac, 4),
            "loader_wait_ok": frac <= LOADER_WAIT_TRIPWIRE_FRAC,
        }
        return res

    def sweep(self, cells: Sequence[PipelineCell], epochs: int = 1,
              warmup_epochs: int = 1, auto_tune: bool = False,
              sweep_jsonl: Optional[str] = None,
              tuned_file: Optional[str] = None) -> dict:
        """Run every cell, report the matrix, optionally persist the best.

        ``sweep_jsonl``: per-cell rows written as telemetry-meta-shaped
        events so ``scripts/telemetry_report.py <file>`` renders the
        pipeline table from the artifact alone."""
        tel = telemetry.get()
        results: List[dict] = []
        writer = open(sweep_jsonl, "w") if sweep_jsonl else None
        try:
            for cell in cells:
                logger.info("pipeline sweep: cell %s ...", cell.label)
                res = self.run_cell(cell, epochs=epochs,
                                    warmup_epochs=warmup_epochs)
                logger.info(
                    "pipeline sweep: %s -> %.1f imgs/s (loader_wait %.2fs,"
                    " dispatch %.2fs, fetch %.2fs, assembly %.2fs)",
                    cell.label, res["imgs_per_sec"], res["loader_wait_s"],
                    res["dispatch_s"], res["fetch_stall_s"],
                    res["assembly_wait_s"])
                tel.meta("pipeline_cell", **res)
                if writer:
                    writer.write(json.dumps(
                        {"kind": "meta", "name": "pipeline_cell", "rank": 0,
                         "fields": res}) + "\n")
                    writer.flush()
                results.append(res)
        finally:
            if writer:
                writer.close()
        best = max(results, key=lambda r: r["imgs_per_sec"])
        out = {"cells": results, "best": best,
               "registry": self.registry.snapshot()}
        if not best["loader_wait_ok"]:
            logger.warning(
                "pipeline sweep: best cell %s still loader-bound "
                "(loader_wait %.0f%% of wall > %.0f%% tripwire)",
                best["cell"], 100 * best["loader_wait_frac"],
                100 * LOADER_WAIT_TRIPWIRE_FRAC)
        if auto_tune:
            cell = PipelineCell(best["k"], best["workers"],
                                best["prefetch"], best["device_prep"])
            path = save_tuned(self.cfg, cell, best, path=tuned_file)
            out["tuned_file"] = path
            out["tuned"] = load_tuned(self.cfg, path=path)
            logger.info("pipeline sweep: tuned cell %s persisted to %s",
                        best["cell"], path)
        return out


def apply_tuned_to_args(args, cfg: Config,
                        path: Optional[str] = None) -> Config:
    """Boot a train driver into the persisted tuned cell.

    Explicit user flags win per field: only fields the user left at their
    parser defaults are overridden.  Returns the (possibly) updated
    config; ``args.steps_per_dispatch`` is mutated in place (k is a fit
    argument, not config state)."""
    tuned = load_tuned(cfg, path=path)
    if tuned is None:
        logger.warning(
            "--tuned-pipeline: no tuned cell for this config under %s — "
            "run `%s` first; continuing with the configured pipeline",
            path or tuned_path(), TUNE_COMMAND)
        return cfg
    tpu_over = {}
    if getattr(args, "loader_workers", None) is None:
        tpu_over["LOADER_WORKERS"] = int(tuned["workers"])
    if getattr(args, "prefetch", None) is None:
        tpu_over["PREFETCH"] = int(tuned["prefetch"])
    if not getattr(args, "device_prep", False):
        tpu_over["DEVICE_PREP"] = bool(tuned["device_prep"])
    if getattr(args, "steps_per_dispatch", 1) == 1:
        args.steps_per_dispatch = int(tuned["k"])
    if tpu_over:
        cfg = cfg.replace(tpu=dataclasses.replace(cfg.tpu, **tpu_over))
    logger.info(
        "tuned pipeline: k=%d workers=%d prefetch=%d device_prep=%s "
        "(%.1f imgs/s when tuned)",
        getattr(args, "steps_per_dispatch", 1), cfg.tpu.LOADER_WORKERS,
        cfg.tpu.PREFETCH, cfg.tpu.DEVICE_PREP,
        tuned.get("imgs_per_sec", 0.0))
    return cfg


def parse_cells(k_list: Sequence[int], workers_list: Sequence[int],
                prefetch_list: Sequence[int],
                device_prep: Sequence[bool] = (False,)) -> List[PipelineCell]:
    """Cartesian product in deterministic order (k-major — step-program
    reuse groups neighboring cells)."""
    return [PipelineCell(k, w, p, dp)
            for k in k_list for w in workers_list
            for p in prefetch_list for dp in device_prep]


def _int_list(spec: str) -> List[int]:
    """Comma-separated ints ("0,2,4") -> [0, 2, 4]."""
    return [int(tok) for tok in spec.split(",") if tok.strip()]


def main(argv: Optional[Sequence[str]] = None) -> dict:
    """Sweep the matrix through the real train hot loop on a synthetic
    roidb — fresh AnchorLoader per cell, one shared step-program cache —
    and print one JSON line: every cell's imgs/s with its loader_wait /
    dispatch / fetch_stall / assembly_wait breakdown, and the best.
    ``--auto-tune`` persists the winner next to the program cache, where
    ``train_end2end.py --tuned-pipeline`` finds it."""
    import argparse

    from mx_rcnn_tpu.compile import setup_compile_cache
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.data.synthetic import SyntheticDataset
    from mx_rcnn_tpu.tools.common import parse_cfg_overrides

    ap = argparse.ArgumentParser(
        prog="python -m mx_rcnn_tpu.train.pipeline", description=main.__doc__)
    ap.add_argument("--network", default="resnet101",
                    help="config preset (e.g. resnet101, resnet101_fpn)")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--k-list", default="1,2",
                    help="comma list of steps-per-dispatch group sizes")
    ap.add_argument("--workers-list", default="0,2",
                    help="comma list of loader worker counts")
    ap.add_argument("--prefetch-list", default="2",
                    help="comma list of prefetch queue depths")
    ap.add_argument("--device-prep", action="store_true",
                    help="sweep device-side preprocessing as a matrix axis "
                         "(each k×w×p cell runs host-prep AND device-prep)")
    ap.add_argument("--auto-tune", action="store_true",
                    help="persist the winning cell next to the program "
                         "cache (train_end2end.py / train_alternate.py "
                         "--tuned-pipeline reads it)")
    ap.add_argument("--pipeline-images", type=int, default=32,
                    help="synthetic roidb size per epoch")
    ap.add_argument("--pipeline-epochs", type=int, default=1,
                    help="measured epochs per cell (one extra warmup epoch "
                         "always runs first)")
    ap.add_argument("--sweep-out", default="",
                    help="per-cell JSONL path (telemetry-meta-shaped rows; "
                         "scripts/telemetry_report.py renders the table).  "
                         "Default: pipeline_sweep.jsonl next to the program "
                         "cache")
    ap.add_argument("--cfg", action="append", default=[],
                    help="config override PATH=VALUE (tools/common.py "
                         "syntax); the tuned cell is keyed by the config's "
                         "digest, so pass what the train run will pass")
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = generate_config(args.network, "PascalVOC",
                          **parse_cfg_overrides(args.cfg))
    # pixel scale as the drivers' --synthetic sets it: same digest
    cfg = cfg.replace(network=dataclasses.replace(
        cfg.network, PIXEL_STDS=(127.0, 127.0, 127.0)))
    roidb = SyntheticDataset(num_images=args.pipeline_images, height=600,
                             width=800).gt_roidb()
    cells = parse_cells(_int_list(args.k_list), _int_list(args.workers_list),
                        _int_list(args.prefetch_list),
                        device_prep=((False, True) if args.device_prep
                                     else (False,)))
    sweep_out = args.sweep_out or os.path.join(
        os.path.dirname(tuned_path()), "pipeline_sweep.jsonl")
    os.makedirs(os.path.dirname(os.path.abspath(sweep_out)), exist_ok=True)
    res = PipelineSweep(cfg, roidb, batch=args.batch).sweep(
        cells, epochs=args.pipeline_epochs, warmup_epochs=1,
        auto_tune=args.auto_tune, sweep_jsonl=sweep_out)
    reg = res.pop("registry")
    # programs stays flat across cells that share k (no per-cell compile)
    res.update(programs=len(reg.get("programs", [])),
               registry_counters=reg.get("counters", {}),
               sweep_jsonl=sweep_out)
    print(json.dumps(res))
    return res


if __name__ == "__main__":
    main()
