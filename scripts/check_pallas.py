#!/usr/bin/env python
"""Kernel-vs-oracle equivalence + timing on the REAL TPU chip — the long
hand-run sweep (odd shapes, adversarial structure, the vmap rule, the
fused assign kernel, timings).

Run manually on the chip.  The two guards that run by themselves are
narrower: tests/test_tpu_kernels.py COMPILES the kernels for a described
v5e at production shapes (pytest runs on the CPU mesh, where ``nms_pallas``
delegates to the oracle, so nothing there can execute a kernel), and
``chip_smoke.py``'s kernels phase checks NMS equality on the chip at the
12000->2000 and 6000->300 contracts.  The assert below is the rule for
every chip script: fail, never fall back.  Exits nonzero on any mismatch.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from mx_rcnn_tpu.kernels.nms_pallas import nms_pallas
from mx_rcnn_tpu.ops.nms import nms_padded

assert jax.default_backend() == "tpu", "run on the TPU chip"


def gen(n, seed, spread=800.0, size=150.0):
    rng = np.random.RandomState(seed)
    ctr = rng.rand(n, 2) * spread
    wh = rng.rand(n, 2) * size + 10
    boxes = np.concatenate([ctr - wh / 2, ctr + wh / 2], 1).astype(np.float32)
    scores = np.sort(rng.rand(n).astype(np.float32))[::-1].copy()
    return jnp.asarray(boxes), jnp.asarray(scores)


fails = 0
for seed in range(5):
    for n, max_out, thresh in ((2048, 300, 0.7), (6000, 300, 0.7),
                               (12000, 2000, 0.7), (4000, 100, 0.3),
                               (100, 300, 0.5),   # n < max_out shape contract
                               (4097, 300, 0.7),  # pad-boundary crossing
                               (4000, 300, 0.99),  # almost nothing suppressed
                               (4000, 300, 0.01)):  # almost all suppressed
        boxes, scores = gen(n, seed)
        valid = jnp.asarray(np.random.RandomState(seed).rand(n) > 0.02)
        ki_p, km_p = jax.device_get(nms_pallas(boxes, scores, max_out=max_out,
                                               iou_thresh=thresh, valid=valid))
        ki_r, km_r = jax.device_get(nms_padded(boxes, scores, max_out=max_out,
                                               iou_thresh=thresh, valid=valid))
        ok = (km_p.sum() == km_r.sum()
              and np.array_equal(ki_p[km_p], ki_r[km_r]))
        if not ok:
            fails += 1
            print(f"MISMATCH n={n} max_out={max_out} t={thresh} seed={seed}: "
                  f"kept {km_p.sum()} vs {km_r.sum()}")

# adversarial structure: exact ties / identical boxes / all-invalid
box1 = jnp.tile(jnp.asarray([[10., 10., 60., 60.]], jnp.float32), (512, 1))
sc1 = jnp.asarray(np.sort(np.random.RandomState(0).rand(512)
                          .astype(np.float32))[::-1].copy())
for name, (b, s, mo, t, v) in {
    "identical-boxes": (box1, sc1, 300, 0.7, None),
    "all-invalid": (box1, sc1, 300, 0.7, jnp.zeros((512,), bool)),
    "single-box": (box1[:1], sc1[:1], 300, 0.7, None),
}.items():
    ki_p, km_p = jax.device_get(nms_pallas(b, s, max_out=mo, iou_thresh=t,
                                           valid=v))
    ki_r, km_r = jax.device_get(nms_padded(b, s, max_out=mo, iou_thresh=t,
                                           valid=v))
    if km_p.sum() != km_r.sum() or not np.array_equal(ki_p[km_p], ki_r[km_r]):
        fails += 1
        print(f"MISMATCH [{name}]: kept {km_p.sum()} vs {km_r.sum()}")
# batched path (vmap over images — the detector's B>1 shape; exercises the
# custom_vmap → lax.map rule, which Mosaic can't auto-batch)
bb = jnp.stack([gen(2048, s)[0] for s in range(3)])
ss = jnp.stack([gen(2048, s)[1] for s in range(3)])
# per-image DIFFERENT invalid holes: a batching-rule regression that drops
# or broadcasts the valid mask must fail this, not just the all-True case
vv = jnp.stack([jnp.asarray(np.random.RandomState(100 + s).rand(2048) > 0.05)
                for s in range(3)])
ki_b, km_b = jax.device_get(jax.vmap(
    lambda b, s, v: nms_pallas(b, s, max_out=300, iou_thresh=0.7, valid=v)
)(bb, ss, vv))
for b in range(3):
    ki_r, km_r = jax.device_get(nms_padded(bb[b], ss[b], max_out=300,
                                           iou_thresh=0.7, valid=vv[b]))
    if km_b[b].sum() != km_r.sum() or not np.array_equal(
            ki_b[b][km_b[b]], ki_r[km_r]):
        fails += 1
        print(f"MISMATCH [vmap b={b}]: kept {km_b[b].sum()} vs {km_r.sum()}")

# ---- fused assign-IoU reductions (kernels/assign_pallas.py) ------------
# ULP-level parity contract (see kernel docstring): floats to ~2 ulp,
# discrete outputs exact away from ULP-boundaries.
from mx_rcnn_tpu.kernels.assign_pallas import assign_reduce_pallas
from mx_rcnn_tpu.ops.anchors import all_anchors, generate_anchors
from mx_rcnn_tpu.ops.boxes import bbox_overlaps

ULP = 3e-7
for fh, fw, stride, n_gt, seed in ((38, 64, 16, 20, 0), (38, 64, 16, 0, 1),
                                   (152, 256, 4, 50, 2)):
    rng = np.random.RandomState(seed)
    anchors = all_anchors(fh, fw, stride, generate_anchors())
    im_h, im_w = fh * stride, fw * stride
    gt = np.zeros((100, 4), np.float32)
    for i in range(n_gt):
        x1, y1 = rng.rand(2) * np.array([im_w - 200, im_h - 200])
        gt[i] = [x1, y1, x1 + 20 + rng.rand() * 160, y1 + 20 + rng.rand() * 160]
    valid = np.arange(100) < n_gt
    inside = ((anchors[:, 0] >= 0) & (anchors[:, 1] >= 0)
              & (anchors[:, 2] < im_w) & (anchors[:, 3] < im_h))
    ov = np.asarray(bbox_overlaps(jnp.asarray(anchors), jnp.asarray(gt)))
    ov = np.where(valid[None, :], ov, -1.0)
    mx, am = ov.max(1), ov.argmax(1)
    ov_in = np.where(inside[:, None], ov, -1.0)
    gm = ov_in.max(0)
    tie = ((ov_in == gm[None, :]) & valid[None, :] & (gm[None, :] > 0)).any(1)
    k_mx, k_am, k_gm, k_tie = jax.device_get(assign_reduce_pallas(
        jnp.asarray(anchors), jnp.asarray(gt), jnp.asarray(valid),
        jnp.asarray(inside)))
    # distances over VALID columns only — padded columns' -1.0 sentinels
    # sit at distance 0 of gm and would mark every anchor marginal,
    # making the discrete checks vacuous (test_assign_sample.py pitfall)
    near_tie = (np.abs(ov[:, valid] - ov.max(1, keepdims=True))
                < ULP).sum(1) > 1
    near_gm = ((np.abs(ov[:, valid] - gm[valid][None, :]) < ULP).any(1)
               if valid.any() else np.zeros(ov.shape[0], bool))
    marginal = near_tie | near_gm
    ok = (np.allclose(k_mx, mx, rtol=0, atol=ULP)
          and np.allclose(k_gm, gm, rtol=0, atol=ULP)
          and not ((k_am != am) & ~marginal).any()
          and not ((k_tie != tie) & ~marginal).any())
    if not ok:
        fails += 1
        print(f"MISMATCH [assign fh={fh} n_gt={n_gt}]: "
              f"mx {np.abs(k_mx - mx).max():.2e} "
              f"am {((k_am != am) & ~marginal).sum()} "
              f"tie {((k_tie != tie) & ~marginal).sum()}")

print("equivalence:", "FAIL" if fails else "OK")

# timing (chained, fence by readback)
boxes, scores = gen(12000, 0)
for name, f in (("pallas", lambda: nms_pallas(boxes, scores, max_out=2000,
                                              iou_thresh=0.7)),
                ("scan  ", lambda: nms_padded(boxes, scores, max_out=2000,
                                              iou_thresh=0.7))):
    r = f()
    jax.block_until_ready(r)
    t0 = time.time()
    for _ in range(20):
        r = f()
    _ = np.asarray(jax.device_get(r[0]))[0]
    print(f"{name} 12000->2000: {(time.time() - t0) / 20 * 1000:.1f} ms")

# timing: fused assign kernel vs dense XLA reductions near FPN scale
# (P2 dominates FPN's 155 520 concatenated anchors; G = 100 like COCO)
anchors_t = jnp.asarray(all_anchors(152, 256, 4,
                                    generate_anchors(scales=(8,))))
rng = np.random.RandomState(0)
gt_t = np.zeros((100, 4), np.float32)
for i in range(60):
    x1, y1 = rng.rand(2) * np.array([800, 400])
    gt_t[i] = [x1, y1, x1 + 20 + rng.rand() * 160, y1 + 20 + rng.rand() * 160]
gt_t = jnp.asarray(gt_t)
valid_t = jnp.asarray(np.arange(100) < 60)
inside_t = jnp.asarray(np.random.RandomState(1).rand(
    anchors_t.shape[0]) > 0.3)


@jax.jit
def dense_reduce(anchors, gt, gv, ins):
    ov = bbox_overlaps(anchors, gt)
    ov = jnp.where(gv[None, :], ov, -1.0)
    ov_in = jnp.where(ins[:, None], ov, -1.0)
    gm = jnp.max(ov_in, axis=0)
    return (jnp.max(ov, axis=1), jnp.argmax(ov, axis=1), gm,
            jnp.any((ov_in == gm[None, :]) & gv[None, :]
                    & (gm[None, :] > 0), axis=1))


for name, f in (("assign fused", lambda: assign_reduce_pallas(
                    anchors_t, gt_t, valid_t, inside_t)),
                ("assign dense", lambda: dense_reduce(
                    anchors_t, gt_t, valid_t, inside_t))):
    r = f()
    jax.block_until_ready(r)
    t0 = time.time()
    for _ in range(50):
        r = f()
    _ = np.asarray(jax.device_get(r[0]))[0]
    print(f"{name} @116736x100: {(time.time() - t0) / 50 * 1000:.2f} ms")

raise SystemExit(1 if fails else 0)
