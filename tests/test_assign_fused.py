"""Fused Pallas assign-IoU reductions vs the dense XLA path — parity in
Pallas interpret mode on CPU (the on-chip gate is scripts/check_pallas.py).

Parity is ULP-level, not bitwise: compilers contract the kernel's FMA
chains differently per fusion context (the pallas interpreter jit-compiles
the kernel body, so even "eager" kernel calls see contraction), so float
outputs are compared to ~1 ULP and discrete outputs (argmax, tie, labels)
must agree except where the decision is within ~1 ULP of a boundary.
EXACT ties (duplicate gt boxes) are layout-stable and asserted exactly."""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from mx_rcnn_tpu.kernels.assign_pallas import assign_reduce_pallas
from mx_rcnn_tpu.ops.anchors import all_anchors, generate_anchors
from mx_rcnn_tpu.ops.assign_anchor import assign_anchor
from mx_rcnn_tpu.ops.boxes import bbox_overlaps

MAX_GT = 16
ULP = 3e-7  # ~2 f32 ulp at iou scale (≤1.0)


def _dense(anchors, gt, valid, inside):
    ov = np.asarray(bbox_overlaps(jnp.asarray(anchors), jnp.asarray(gt)))
    ov = np.where(valid[None, :], ov, -1.0)
    mx = ov.max(axis=1)
    am = ov.argmax(axis=1)
    ov_in = np.where(inside[:, None], ov, -1.0)
    gm = ov_in.max(axis=0)
    tie = ((ov_in == gm[None, :]) & valid[None, :] & (gm[None, :] > 0)).any(1)
    return ov, mx, am, gm, tie


def _case(rng, n_gt, fh=10, fw=12, stride=16):
    anchors = all_anchors(fh, fw, stride, generate_anchors(scales=(1, 2, 4)))
    im_h, im_w = fh * stride, fw * stride
    gt = np.zeros((MAX_GT, 4), np.float32)
    for i in range(n_gt):
        x1, y1 = rng.rand(2) * np.array([im_w - 80, im_h - 80])
        gt[i] = [x1, y1, x1 + 20 + rng.rand() * 60, y1 + 20 + rng.rand() * 60]
    valid = np.arange(MAX_GT) < n_gt
    inside = ((anchors[:, 0] >= 0) & (anchors[:, 1] >= 0)
              & (anchors[:, 2] < im_w) & (anchors[:, 3] < im_h))
    return anchors, gt, valid, inside


def _check_discrete(ov, gm, valid, ref_disc, got_disc, name):
    """Discrete outputs must match except where the deciding comparison is
    within ~1 ULP (ties between columns, or against gt_max).  Distances are
    taken over VALID gt columns only: padded columns carry the sentinel
    -1.0 in both ov and gm, whose distance-0 'tie' would mark every anchor
    marginal and make the assertion vacuous (the test_assign_sample.py
    bf16-test pitfall)."""
    ovv = ov[:, valid]
    gmv = gm[valid]
    near_tie = (np.abs(ovv - ov.max(1, keepdims=True)) < ULP).sum(1) > 1
    near_gtmax = (np.abs(ovv - gmv[None, :]) < ULP).any(1) if valid.any() \
        else np.zeros(ov.shape[0], bool)
    marginal = near_tie | near_gtmax
    bad = (ref_disc != got_disc) & ~marginal
    assert not bad.any(), f"{name}: {bad.sum()} non-marginal mismatches"


def test_jitted_matches_dense_to_ulp(rng):
    for n_gt in (0, 1, 5, MAX_GT):
        anchors, gt, valid, inside = _case(rng, n_gt)
        ov, mx, am, gm, tie = _dense(anchors, gt, valid, inside)
        k_mx, k_am, k_gm, k_tie = assign_reduce_pallas(
            jnp.asarray(anchors), jnp.asarray(gt), jnp.asarray(valid),
            jnp.asarray(inside), interpret=True)
        np.testing.assert_allclose(np.asarray(k_mx), mx, rtol=0, atol=ULP)
        np.testing.assert_allclose(np.asarray(k_gm), gm, rtol=0, atol=ULP)
        _check_discrete(ov, gm, valid, am, np.asarray(k_am), "argmax")
        _check_discrete(ov, gm, valid, tie, np.asarray(k_tie), "tie")


def test_duplicate_gt_tie_breaks_like_argmax(rng):
    """Two identical gt boxes: argmax must pick the smaller index and BOTH
    columns' tie predicate must fire — an EXACT tie is layout-stable (the
    two columns share identical arithmetic), so equality is required."""
    anchors, gt, valid, inside = _case(rng, 2)
    gt[1] = gt[0]
    ov, mx, am, gm, tie = _dense(anchors, gt, valid, inside)
    k_mx, k_am, k_gm, k_tie = assign_reduce_pallas(
        jnp.asarray(anchors), jnp.asarray(gt), jnp.asarray(valid),
        jnp.asarray(inside), interpret=True)
    np.testing.assert_array_equal(np.asarray(k_am), am.astype(np.int32))
    np.testing.assert_array_equal(np.asarray(k_tie), tie)


def test_assign_anchor_fused_path_matches_dense(rng):
    """Whole-op parity: labels agree except ULP-marginal anchors; on rows
    where both paths say fg, targets are close (same gt unless ULP-tied)."""
    anchors, gt, valid, inside = _case(rng, 5)
    im_h, im_w = 160, 192
    args = (jnp.asarray(anchors), jnp.asarray(gt), jnp.asarray(valid),
            jnp.float32(im_h), jnp.float32(im_w), jax.random.PRNGKey(3))
    kw = dict(batch_size=100000, fg_fraction=1.0)  # no subsample noise
    dense = assign_anchor(*args, fused=False, **kw)
    fusedk = assign_anchor(*args, fused=True, _fused_interpret=True, **kw)
    ov, mx, am, gm, tie = _dense(anchors, gt, valid, inside)
    l_d = np.asarray(dense["label"])
    l_k = np.asarray(fusedk["label"])
    near_thr = (np.abs(mx - 0.7) < ULP) | (np.abs(mx - 0.3) < ULP)
    near_gtmax = (np.abs(ov[:, valid] - gm[valid][None, :]) < ULP).any(1)
    bad = (l_d != l_k) & ~(near_thr | near_gtmax)
    assert not bad.any(), f"{bad.sum()} non-marginal label flips"
    stable = ((np.sort(ov, 1)[:, -1] - np.sort(ov, 1)[:, -2]) > ULP)
    both_fg = (l_d == 1) & (l_k == 1) & stable
    np.testing.assert_array_equal(
        np.asarray(dense["bbox_target"])[both_fg],
        np.asarray(fusedk["bbox_target"])[both_fg])


def test_fused_vmap_batches_via_map(rng):
    """Batched (vmapped) call lowers through the custom_vmap rule and
    matches per-image jitted results to ULP."""
    anchors, gt0, valid0, inside = _case(rng, 3)
    _, gt1, valid1, _ = _case(rng, 6)
    gts = jnp.stack([jnp.asarray(gt0), jnp.asarray(gt1)])
    valids = jnp.stack([jnp.asarray(valid0), jnp.asarray(valid1)])
    out = jax.vmap(
        lambda g, v: assign_reduce_pallas(
            jnp.asarray(anchors), g, v, jnp.asarray(inside), interpret=True)
    )(gts, valids)
    for b, (g, v) in enumerate([(gt0, valid0), (gt1, valid1)]):
        ov, mx, am, gm, tie = _dense(anchors, g, np.asarray(v), inside)
        np.testing.assert_allclose(np.asarray(out[0][b]), mx, rtol=0, atol=ULP)
        np.testing.assert_allclose(np.asarray(out[2][b]), gm, rtol=0, atol=ULP)
        _check_discrete(ov, gm, np.asarray(v), am, np.asarray(out[1][b]), f"argmax[{b}]")
        _check_discrete(ov, gm, np.asarray(v), tie, np.asarray(out[3][b]), f"tie[{b}]")


import pytest  # noqa: E402


@pytest.mark.parametrize("batch", [4, 3])
def test_fused_vmap_on_a_mesh_shard_maps_itself(rng, batch):
    """Traced for a mesh (``MeshPlan.traced``), the batching rule wraps its
    per-image map in a ``shard_map`` — XLA cannot partition a Mosaic
    kernel, and jax refuses to lower one for several devices outside one.
    Same results as the plain map, whether the data axis divides the
    batch (each device takes its rows) or not (computed whole on each)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mx_rcnn_tpu.parallel import make_mesh

    plan = make_mesh(jax.devices()[:4], data=4)
    anchors, _, _, inside = _case(rng, 1)
    cases = [_case(rng, n)[1:3] for n in range(2, 2 + batch)]
    gts = jnp.stack([jnp.asarray(g) for g, _ in cases])
    valids = jnp.stack([jnp.asarray(v) for _, v in cases])

    def batched(g, v):
        return jax.vmap(lambda gi, vi: assign_reduce_pallas(
            jnp.asarray(anchors), gi, vi, jnp.asarray(inside),
            interpret=True))(g, v)

    plain = jax.jit(batched)
    on_mesh = jax.jit(plan.traced(batched))
    rows = P("data") if batch % 4 == 0 else P()
    placed = [jax.device_put(x, NamedSharding(plan.mesh, rows))
              for x in (gts, valids)]
    text = on_mesh.lower(*placed).as_text()
    assert "sdy.manual_computation" in text   # the shard_map is there
    assert "sdy.manual_computation" not in plain.lower(gts, valids).as_text()
    for got, want in zip(on_mesh(*placed), plain(gts, valids)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
