"""Checkpointing — reference ``rcnn/core/callback.py: do_checkpoint`` +
``rcnn/utils/{load,save}_model.py``, on orbax.

Contracts kept:

* **De-normalize at save**: training regresses bbox targets normalized by
  (BBOX_MEANS, BBOX_STDS) — a RoI cascade's stage t by 1 / its own
  ``ROI_CASCADE_WEIGHTS[t - 1]`` —; ``do_checkpoint`` folds them into each
  ``bbox_pred`` layer's weights/bias before writing, so the saved
  checkpoint predicts raw deltas and inference needs no de-normalization.  On resume,
  the inverse fold is applied (reference train_end2end resume path).
* Epoch-indexed checkpoints under ``prefix`` (``prefix-%04d.params`` →
  ``{prefix}/epoch_{n:04d}`` orbax directories), plus step-level resume —
  the SURVEY §5 failure-detection upgrade, now implemented: mid-epoch
  step checkpoints live under ``{prefix}/steps/{epoch·STRIDE+consumed}``
  (atomic orbax writes, rolling window) and carry the RAW training
  parametrization + optimizer state + the trainer's RNG key, so
  ``fit(auto_resume)`` restores the exact step the run died at.  Epoch
  checkpoints keep the de-normalized inference contract; step
  checkpoints are resume-only artifacts and skip the fold entirely.
* Saves retry transient I/O errors with exponential backoff
  (``resilience.retry_io`` — ``checkpoint/retry`` telemetry counter).
"""

from __future__ import annotations

import functools
import os
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import orbax.checkpoint as ocp

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.train.resilience import (decode_step_key, encode_step_key,
                                          retry_io)


def _bbox_fold(params, means, stds, invert: bool, stage_stds=None):
    """Fold (or unfold) target normalization into the bbox_pred layers.

    kernel: (D, 4N); bias: (4N,), N boxes a RoI (K, or 1 class-agnostic).
    saved = trained * stds + means(bias only); invert recovers the trained
    parametrization.  ``stage_stds`` ({output module: stds}) overrides
    ``stds`` for the named modules' layers: a RoI cascade's stages.
    """
    def fold(path, leaf):
        names = [getattr(e, "key", str(e)) for e in path]
        if "bbox_pred" not in names or names[-1] not in ("kernel", "bias"):
            return leaf
        at = names.index("bbox_pred")
        owner = names[at - 1] if at else None
        s = (stage_stds or {}).get(owner, stds)
        reps = leaf.shape[-1] // 4
        stds_t = jnp.asarray(np.tile(np.asarray(s, np.float32), reps))
        means_t = jnp.asarray(np.tile(np.asarray(means, np.float32), reps))
        if names[-1] == "kernel":
            return leaf / stds_t[None, :] if invert else leaf * stds_t[None, :]
        return (leaf - means_t) / stds_t if invert else leaf * stds_t + means_t

    return jax.tree_util.tree_map_with_path(fold, params)


def roi_cascade_stds(cfg) -> dict:
    """{stage output module: its targets' stds}: a RoI cascade's stage t
    (``rcnn_out``, ``rcnn_out2``, ...) regresses targets normalised by 1 /
    ``network.ROI_CASCADE_WEIGHTS[t - 1]`` (models/fpn.py
    ``_roi_cascade_losses``); empty on every other network."""
    net = cfg.network
    if net.ROI_CASCADE_STAGES <= 1:
        return {}
    return {("rcnn_out" if t == 1 else f"rcnn_out{t}"):
            tuple(1.0 / w for w in net.ROI_CASCADE_WEIGHTS[t - 1])
            for t in range(1, net.ROI_CASCADE_STAGES + 1)}


def denormalize_for_save(params, cfg):
    return _bbox_fold(params, cfg.TRAIN.BBOX_MEANS, cfg.TRAIN.BBOX_STDS,
                      invert=False, stage_stds=roi_cascade_stds(cfg))


def normalize_for_train(params, cfg):
    return _bbox_fold(params, cfg.TRAIN.BBOX_MEANS, cfg.TRAIN.BBOX_STDS,
                      invert=True, stage_stds=roi_cascade_stds(cfg))


class CheckpointManager:
    """Thin orbax wrapper with the reference's epoch naming, plus the
    step-checkpoint tier (``{prefix}/steps``) for mid-epoch resume."""

    STEP_SUBDIR = "steps"

    def __init__(self, prefix: str, max_to_keep: Optional[int] = None,
                 step_keep: int = 2, io_retries: int = 3,
                 io_backoff_s: float = 0.5):
        self.prefix = os.path.abspath(prefix)
        os.makedirs(self.prefix, exist_ok=True)
        self._mgr = ocp.CheckpointManager(
            self.prefix,
            options=ocp.CheckpointManagerOptions(max_to_keep=max_to_keep,
                                                 create=True),
        )
        self._step_keep = step_keep
        self._retry = functools.partial(retry_io, retries=io_retries,
                                        backoff_s=io_backoff_s)
        self._steps_mgr = None  # lazy: most runs never write step ckpts

    def _steps(self) -> ocp.CheckpointManager:
        if self._steps_mgr is None:
            self._steps_mgr = ocp.CheckpointManager(
                os.path.join(self.prefix, self.STEP_SUBDIR),
                options=ocp.CheckpointManagerOptions(
                    max_to_keep=self._step_keep, create=True),
            )
        return self._steps_mgr

    def save_epoch(self, epoch: int, params, cfg, opt_state=None,
                   step: int = 0):
        """``do_checkpoint`` analogue: de-normalized params + raw training
        state for exact resume."""
        payload = {
            "params": jax.device_get(denormalize_for_save(params, cfg)),
            "step": step,
        }
        if opt_state is not None:
            payload["opt_state"] = jax.device_get(opt_state)

        def do_save():
            self._mgr.save(epoch, args=ocp.args.StandardSave(payload))
            self._mgr.wait_until_finished()

        self._retry(do_save, what=f"epoch checkpoint {epoch}")
        if jax.process_index() == 0:
            logger.info("Saved checkpoint epoch %d -> %s", epoch, self.prefix)

    def available_epochs(self) -> list:
        return sorted(self._mgr.all_steps())

    def load_epoch(self, epoch: int, cfg, for_training: bool = True,
                   abstract_payload=None):
        """Returns (params, opt_state_or_None, step).

        For exact training resume pass ``abstract_payload`` — a pytree
        skeleton matching what was saved, e.g.
        ``{"params": params_like, "opt_state": tx.init(params_like),
        "step": 0}`` — so orbax restores the true optax state classes
        (target-less restore returns raw dicts optax cannot consume).
        """
        have = self.available_epochs()
        if epoch not in have:
            raise FileNotFoundError(
                f"no checkpoint for epoch {epoch} under {self.prefix}; "
                f"epochs present: {have or 'none'} — pass one of those (or "
                f"retrain; the latest is selected by fit(auto_resume))")
        if abstract_payload is not None:
            restored = self._mgr.restore(
                epoch, args=ocp.args.StandardRestore(abstract_payload))
        else:
            # target-less StandardRestore, never a bare restore(): a
            # manager that didn't write the save (fresh process — eval,
            # serving hot-reload) has no handler registered for the
            # item and bare restore() raises KeyError
            restored = self._mgr.restore(epoch,
                                         args=ocp.args.StandardRestore())
        params = restored["params"]
        if for_training:
            params = normalize_for_train(params, cfg)
        return params, restored.get("opt_state"), int(restored.get("step", 0))

    def latest_epoch(self) -> Optional[int]:
        return self._mgr.latest_step()

    # -- step checkpoints (mid-epoch resume; resilience.py contract) -----

    def save_step(self, epoch: int, consumed: int, params, cfg,
                  opt_state=None, step: int = 0, rng_key=None):
        """Step checkpoint at ``consumed`` loader batches into ``epoch``.

        RAW training parametrization (no bbox de-normalize — this is a
        resume-only artifact, never an inference input), plus the
        trainer's RNG key so the resumed per-step key stream continues
        bit-exactly.  ``cfg`` is accepted for signature symmetry with
        ``save_epoch`` but unused.  All ranks must call (orbax barriers).
        """
        del cfg
        payload = {
            "params": jax.device_get(params),
            "step": int(step),
            "epoch": int(epoch),
            "consumed": int(consumed),
        }
        if opt_state is not None:
            payload["opt_state"] = jax.device_get(opt_state)
        if rng_key is not None:
            payload["rng_key"] = np.asarray(jax.device_get(rng_key))
        key = encode_step_key(epoch, consumed)
        mgr = self._steps()

        def do_save():
            mgr.save(key, args=ocp.args.StandardSave(payload))
            mgr.wait_until_finished()

        with telemetry.get().span("checkpoint/step_save"):
            self._retry(do_save,
                        what=f"step checkpoint (epoch {epoch}, "
                             f"batch {consumed})")
        if jax.process_index() == 0:
            logger.info("Saved step checkpoint epoch %d batch %d -> %s/%s",
                        epoch, consumed, self.prefix, self.STEP_SUBDIR)

    def latest_step_checkpoint(self) -> Optional[Tuple[int, int]]:
        """Latest step checkpoint as (epoch, consumed), or None."""
        if not os.path.isdir(os.path.join(self.prefix, self.STEP_SUBDIR)):
            return None
        key = self._steps().latest_step()
        return None if key is None else decode_step_key(key)

    def load_step_checkpoint(self, epoch: int, consumed: int,
                             abstract_payload=None) -> dict:
        """Restore a step checkpoint's full payload (params stay in the
        RAW training parametrization — do NOT ``normalize_for_train``)."""
        key = encode_step_key(epoch, consumed)
        mgr = self._steps()
        if key not in mgr.all_steps():
            have = [decode_step_key(k) for k in sorted(mgr.all_steps())]
            raise FileNotFoundError(
                f"no step checkpoint (epoch {epoch}, batch {consumed}) under "
                f"{self.prefix}/{self.STEP_SUBDIR}; present: {have or 'none'}")
        if abstract_payload is not None:
            return mgr.restore(
                key, args=ocp.args.StandardRestore(abstract_payload))
        # see load_epoch: target-less StandardRestore for fresh-process
        # readers (bare restore() requires the writer's handler registry)
        return mgr.restore(key, args=ocp.args.StandardRestore())

    def latest_resume_point(self) -> Optional[Tuple[str, int, int]]:
        """The furthest position any checkpoint reaches, for auto-resume:
        ``("epoch", E, 0)`` (epoch checkpoint E = start of epoch E) or
        ``("step", E, C)`` (C batches into epoch E); None when the prefix
        holds no checkpoints.  A stale step checkpoint from before the
        latest epoch checkpoint loses the comparison, so a finished epoch
        always wins over its own mid-epoch saves."""
        cands = []
        e = self.latest_epoch()
        if e is not None:
            cands.append((e, 0, "epoch"))
        s = self.latest_step_checkpoint()
        if s is not None:
            cands.append((s[0], s[1], "step"))
        if not cands:
            return None
        ep, cons, kind = max(cands)
        return kind, ep, cons


def save_params_npz(path: str, params) -> None:
    """Flat .npz export (the deployment artifact; also the pretrained-backbone
    interchange format — utils/load_model.py reads it back)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    out = {}
    for p, leaf in flat:
        key = "/".join(getattr(e, "key", str(e)) for e in p)
        out[key] = np.asarray(jax.device_get(leaf))
    np.savez(path, **out)


def load_params_npz(path: str):
    """Inverse of save_params_npz -> nested dict pytree."""
    data = np.load(path)
    tree: dict = {}
    for key in data.files:
        parts = key.split("/")
        node = tree
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = jnp.asarray(data[key])
    return tree
