"""Pallas TPU fused assign-IoU reductions — the RPN anchor-assignment
analogue of the reference's ``bbox_overlaps_cython`` + numpy reductions
(``rcnn/io/rpn.py: assign_anchor``), re-designed for the TPU memory system.

``ops/assign_anchor.py`` needs four reductions of the (N, G) anchor×gt IoU
matrix: per-anchor max and argmax, per-gt max over inside anchors, and the
"anchor ties some gt's max" predicate.  The dense path materializes the
matrix once and reads it three times — at FPN's N=155 520 concatenated
anchors that is ~250 MB of HBM traffic per image and ~2.6 ms/step of the
profiled 21.8 ms (BASELINE.md FPN floor; round-3 confirmed XLA cannot fuse
it further — the traffic is real, not rematerialization).

This kernel never materializes the matrix: IoU is recomputed on the fly
from the (N, 4) anchors and the tiny (G, 4) gt set (the FLOPs are ~300
MFLOP — noise next to 250 MB of bandwidth), so HBM traffic drops to the
anchor reads + (N,) outputs (~2.5 MB, ~100× less).  Two sequential grid
phases share one VMEM scratch:

* **phase 0** sweeps anchor tiles accumulating the per-gt max over INSIDE
  anchors (``gt_max``) — it must finish before the tie predicate exists;
* **phase 1** re-sweeps computing per-anchor max/argmax (first-index tie
  semantics, matching ``jnp.argmax``) and the tie predicate
  ``any_j(iou[i,j] == gt_max[j] & valid[j] & gt_max[j] > 0)``.

Arithmetic is the exact expression tree of ``ops/boxes.bbox_overlaps``
(legacy +1 areas, eps-clamped union, f32).  Parity with the dense path is
ULP-level, not bitwise: compilers may contract mul+add chains into FMAs
differently per fusion context (measured on CPU: jitted vs eager versions
of the SAME expression differ in the last mantissa bit on ~20% of
entries).  The ``==`` tie predicate is computed INSIDE the kernel from
its own iou values, so it is exactly self-consistent; cross-path label
flips are confined to anchors whose IoU sits within ~1 ULP of a
threshold or per-gt tie (tests/test_assign_fused.py bounds this).

Non-TPU backends fall back to the dense path (Mosaic only lowers on TPU);
CI parity runs this kernel in Pallas interpret mode
(tests/test_assign_fused.py), tests/test_tpu_kernels.py compiles it for
a described v5e at the FPN P2 shape, and the on-chip equivalence check is
scripts/check_pallas.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mx_rcnn_tpu.kernels.per_image import map_images

_TILE_N = 2048   # anchors per grid step ((TILE_N, 128) f32 tile = 1 MB VMEM)
_G_PAD = 128     # gt padded to one lane width


def _assign_kernel(anchors_ref, gtt_ref, gtv_ref, inside_ref,
                   maxov_ref, argmax_ref, tie_ref, gtmax_ref, acc_ref):
    p = pl.program_id(0)          # 0: accumulate gt_max; 1: per-anchor outs
    i = pl.program_id(1)
    nt = pl.num_programs(1)

    ab = anchors_ref[:]                       # (TILE_N, 4) f32
    ax1, ay1 = ab[:, 0:1], ab[:, 1:2]         # (TILE_N, 1)
    ax2, ay2 = ab[:, 2:3], ab[:, 3:4]
    gx1 = gtt_ref[0:1, :]                     # (1, G) — gt transposed
    gy1 = gtt_ref[1:2, :]
    gx2 = gtt_ref[2:3, :]
    gy2 = gtt_ref[3:4, :]
    gv = gtv_ref[0:1, :]                      # (1, G) f32 1/0 validity

    # bbox_overlaps' exact expression tree (ops/boxes.py:96-105)
    iw = jnp.maximum(
        jnp.minimum(ax2, gx2) - jnp.maximum(ax1, gx1) + 1.0, 0.0)
    ih = jnp.maximum(
        jnp.minimum(ay2, gy2) - jnp.maximum(ay1, gy1) + 1.0, 0.0)
    inter = iw * ih                           # (TILE_N, G)
    area_a = (ax2 - ax1 + 1.0) * (ay2 - ay1 + 1.0)
    area_g = (gx2 - gx1 + 1.0) * (gy2 - gy1 + 1.0)
    union = jnp.maximum(area_a + area_g - inter, 1e-14)
    iou = jnp.where(gv > 0, inter / union, -1.0)   # invalid gt never wins

    @pl.when(p == 0)
    def _():                                  # accumulate per-gt max
        @pl.when(i == 0)
        def _():
            acc_ref[:] = jnp.full_like(acc_ref, -1.0)

        ins = inside_ref[:]                   # (TILE_N, 1) f32 1/0
        ov_in = jnp.where(ins > 0, iou, -1.0)
        acc_ref[:] = jnp.maximum(acc_ref[:], jnp.max(ov_in, axis=0,
                                                     keepdims=True))

    @pl.when(p == 1)
    def _():                                  # per-anchor outputs
        gt_max = acc_ref[:]                   # (1, G) — final after phase 0
        rowmax = jnp.max(iou, axis=1, keepdims=True)          # (TILE_N, 1)
        eq = iou == rowmax                                    # ties → min id
        colid = jax.lax.broadcasted_iota(jnp.int32, iou.shape, 1)
        argmax = jnp.min(jnp.where(eq, colid, _G_PAD), axis=1,
                         keepdims=True)
        ins = inside_ref[:]
        ov_in = jnp.where(ins > 0, iou, -1.0)
        tie = (ov_in == gt_max) & (gv > 0) & (gt_max > 0)
        maxov_ref[:] = rowmax
        argmax_ref[:] = argmax
        tie_ref[:] = jnp.max(tie.astype(jnp.int32), axis=1, keepdims=True)
        @pl.when(i == nt - 1)
        def _():
            gtmax_ref[:] = gt_max


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _assign_core(anchors, gt_boxes, gt_valid, inside, *, interpret=False):
    n = anchors.shape[0]
    g = gt_boxes.shape[0]
    assert g <= _G_PAD, f"MAX_GT {g} > kernel lane width {_G_PAD}"
    n_pad = _pad_to(n, _TILE_N)

    anchors_p = jnp.zeros((n_pad, 4), jnp.float32).at[:n].set(
        anchors.astype(jnp.float32))
    inside_p = jnp.zeros((n_pad, 1), jnp.float32).at[:n, 0].set(
        inside.astype(jnp.float32))
    gtt = jnp.zeros((4, _G_PAD), jnp.float32).at[:, :g].set(
        gt_boxes.astype(jnp.float32).T)
    gtv = jnp.zeros((1, _G_PAD), jnp.float32).at[0, :g].set(
        gt_valid.astype(jnp.float32))

    grid = (2, n_pad // _TILE_N)
    maxov, argmax, tie, gt_max = pl.pallas_call(
        _assign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_TILE_N, 4), lambda p, i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec((_TILE_N, 1), lambda p, i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((_TILE_N, 1), lambda p, i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_TILE_N, 1), lambda p, i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_TILE_N, 1), lambda p, i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, _G_PAD), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, _G_PAD), jnp.float32)],
        interpret=interpret,
    )(anchors_p, gtt, gtv, inside_p)

    return (maxov[:n, 0], argmax[:n, 0], gt_max[0, :g],
            tie[:n, 0].astype(bool))


_VMAP_CACHE: dict = {}


def _assign_vmappable(interpret: bool):
    """custom_vmap wrapper: Mosaic can't auto-batch the scratch/constant
    block specs, and per-image sweeps are sequential anyway — batch levels
    lower to lax.map over single-image kernel calls (the recursive-rule
    pattern from kernels/nms_pallas.py)."""
    fn = _VMAP_CACHE.get(interpret)
    if fn is not None:
        return fn

    @jax.custom_batching.custom_vmap
    def fn(anchors, gt_boxes, gt_valid, inside):
        return _assign_core(anchors, gt_boxes, gt_valid, inside,
                            interpret=interpret)

    @fn.def_vmap
    def _rule(axis_size, in_batched, anchors, gt_boxes, gt_valid, inside):
        anchors, gt_boxes, gt_valid, inside = (
            a if b else jnp.broadcast_to(a[None], (axis_size,) + a.shape)
            for a, b in zip((anchors, gt_boxes, gt_valid, inside), in_batched)
        )
        # map body calls fn (not _assign_core) so nested vmaps re-enter
        # this rule instead of pushing batching into pallas_call
        out = map_images(fn, (anchors, gt_boxes, gt_valid, inside))
        return out, (True, True, True, True)

    _VMAP_CACHE[interpret] = fn
    return fn


@partial(jax.jit, static_argnames=("interpret",))
def assign_reduce_pallas(anchors, gt_boxes, gt_valid, inside,
                         interpret: bool = False):
    """Fused replacement for the dense IoU reductions in
    ``ops/assign_anchor.py``.

    Returns ``(max_overlap (N,) f32, argmax_gt (N,) i32, gt_max (G,) f32,
    is_gt_argmax (N,) bool)`` with the dense path's exact semantics:
    invalid gt columns masked to −1, per-anchor argmax breaking ties at the
    smallest gt index, ``gt_max`` over inside anchors only, and the tie
    predicate requiring a valid gt with positive max.
    """
    return _assign_vmappable(interpret)(anchors, gt_boxes, gt_valid, inside)
