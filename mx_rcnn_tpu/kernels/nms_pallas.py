"""Pallas TPU NMS — the reference's CUDA bitmask kernel
(``rcnn/cython/nms_kernel.cu``), re-tiled for the TPU memory system.

The CUDA kernel computes a 64-bit suppression bitmask per (box, block) pair
on device and does the greedy sweep on host.  Here both phases stay on
device, and — like the CUDA original — the suppression matrix is BIT-PACKED
(32 consecutive columns per int32 word; signed because Mosaic lacks
unsigned reduces — bit ops are two's-complement safe and extraction masks
after the shift):

* **Phase A** (``_suppress_kernel``): 2D grid over (row tile, col-word
  tile); each step computes the IoU of its (BR) rows against its column
  words and packs ``iou > thresh`` into (BR, CW) words.  The kernel
  iterates 32 unrolled "bit lanes": pass j compares the rows against the
  column set {32w + j : w}, whose boxes are pre-gathered OUTSIDE the
  kernel into row j of a (32, N/32) array — so in-kernel access is a
  contiguous slice, never strided.  Tiles strictly below the diagonal are
  skipped entirely (the sweep only ever reads a row's bits at its own
  block's word and above, and the word-aligned row tiling keeps skipped
  garbage out of every later read).  The packed write is ≤ N²/8 bytes
  (18 MB at N=12k vs 147 MB unpacked), and ~⅓ of the IoU work is skipped
  at this tile shape.
* **Phase B** (``_sweep_kernel``): the greedy sweep, ``_BS``=8 rows per
  step.  Sequential by nature, and the expensive part of earlier versions
  was vector→scalar latency (~16 cross-lane reductions per block).  The
  packed layout kills that: a block's 8 columns are 8-aligned bits of ONE
  word, so suppressed-by-earlier/valid state is read with ONE masked
  reduce each; the 8×8 intra-block dependency table arrives bit-packed in
  SMEM (two words per block, scalar-indexed), so the serial greedy
  resolution runs entirely in scalar registers; ``keep`` is written once
  per block and ``removed`` is updated with one masked OR over the
  (_BS, N/32) row words.  Early termination: selection order is score
  order (sorted input), so once ``max_out`` boxes are kept the remaining
  blocks are predicated off (kept count in SMEM scratch).

Boxes must arrive score-sorted.  Two callers honor that contract: RPN
``propose`` (jax.lax.top_k upstream) and the fused eval post-process
(``ops.nms.nms_ranked`` argsorts per class before delegating here — the
``--device-postprocess`` readback-shrink path, where per-class NMS runs
inside the ``predict_post`` program instead of on the host).  Same greedy
tie/threshold semantics as ``ops.nms.nms_padded`` (suppress when IoU >
thresh, legacy +1 areas), which remains the oracle in tests
(tests/test_nms.py) and on-chip (scripts/check_pallas.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mx_rcnn_tpu.kernels.per_image import map_images

_BR = 256    # row tile (sublane multiple)
_BS = 8      # sweep block: rows resolved per step (8-aligned, divides 32)
_PL = 32     # bits per packed word
# n_pad must satisfy: n_pad % _BR == 0 and (n_pad // _PL) % 128 == 0
_PAD = 4096


def _suppress_kernel(thresh_ref, rbox_ref, cx1_ref, cy1_ref, cx2_ref,
                     cy2_ref, out_ref):
    # 2D grid (row tile, col-word tile).  Tiles strictly below the diagonal
    # are skipped: the sweep reads sup[g, col] only for col ≥ the block's
    # own columns, and stale VMEM in a skipped tile's output only lands in
    # words no later block ever reads (row tiles are word-aligned, so a
    # row's garbage words all lie strictly below every later block's word).
    r = pl.program_id(0)
    c = pl.program_id(1)
    cw = out_ref.shape[1]                # col-word tile width

    @pl.when((c + 1) * cw * _PL > r * _BR)
    def _():
        rb = rbox_ref[:]                     # (BR, 4) f32
        rx1, ry1 = rb[:, 0:1], rb[:, 1:2]    # (BR, 1)
        rx2, ry2 = rb[:, 2:3], rb[:, 3:4]
        ra = (rx2 - rx1 + 1.0) * (ry2 - ry1 + 1.0)
        t = thresh_ref[0]

        acc = jnp.zeros(out_ref.shape, jnp.int32)
        for j in range(_PL):             # unrolled bit-lane loop
            cx1 = cx1_ref[j:j + 1, :]    # (1, CW) — contiguous slice; row j
            cy1 = cy1_ref[j:j + 1, :]    # holds the boxes of columns 32w+j
            cx2 = cx2_ref[j:j + 1, :]
            cy2 = cy2_ref[j:j + 1, :]
            iw = jnp.maximum(
                jnp.minimum(rx2, cx2) - jnp.maximum(rx1, cx1) + 1.0, 0.0)
            ih = jnp.maximum(
                jnp.minimum(ry2, cy2) - jnp.maximum(ry1, cy1) + 1.0, 0.0)
            inter = iw * ih
            ca = (cx2 - cx1 + 1.0) * (cy2 - cy1 + 1.0)
            union = jnp.maximum(ra + ca - inter, 1e-14)
            bits = (inter / union > t).astype(jnp.int32)
            acc = acc | (bits << j)
        out_ref[:] = acc


def _sweep_kernel(max_out_ref, diagp_ref, sup_ref, valid_ref, keep_ref,
                  removed_ref, kept_ref):
    pid = pl.program_id(0)
    w32 = sup_ref.shape[1]
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (1, w32), 1)
    rowid = jax.lax.broadcasted_iota(jnp.int32, (_BS, 1), 0)

    @pl.when(pid == 0)
    def _():
        removed_ref[:] = jnp.zeros_like(removed_ref)
        keep_ref[:] = jnp.zeros_like(keep_ref)
        kept_ref[0] = 0

    def body(i0, _):
        # dynamic sublane access must be 8-aligned: _BS-row slice at _BS·i0
        base = pl.multiple_of(i0 * _BS, _BS)

        @pl.when(kept_ref[0] < max_out_ref[0])
        def _():
            rows8 = sup_ref[pl.ds(base, _BS), :]                  # (_BS, W32)
            g0 = pid * _BR + base
            w0 = g0 // _PL                 # the block's word lane
            j0 = g0 % _PL                  # its first bit (8-aligned)
            blk = g0 // _BS
            wordsel = iota_w == w0                                # (1, W32)
            # ONE vector->scalar reduce each: the word holding all 8
            # column bits of this block
            rm_w = jnp.sum(jnp.where(wordsel, removed_ref[:], 0))
            vd_w = jnp.sum(jnp.where(wordsel, valid_ref[:], 0))
            # 8x8 intra-block table, bit-packed two words per block in
            # SMEM: word k, byte j' (j = 4k + j'), bit i = "accepting row
            # i suppresses row j".  Scalar-indexed loads.
            d_lo = diagp_ref[2 * blk]
            d_hi = diagp_ref[2 * blk + 1]

            # serial greedy resolution, entirely in scalar registers
            acc_bits = 0
            cnt = kept_ref[0]
            for j in range(_BS):                                  # unrolled
                dw = d_hi if j >= 4 else d_lo
                colbits = (dw >> (8 * (j % 4))) & 0xFF
                a_j = (((rm_w >> (j0 + j)) & 1) == 0) & \
                      (((vd_w >> (j0 + j)) & 1) != 0) & \
                      ((colbits & acc_bits) == 0) & \
                      (cnt < max_out_ref[0])
                aji = a_j.astype(jnp.int32)
                acc_bits = acc_bits | (aji << j)
                cnt = cnt + aji

            keep_ref[:] = keep_ref[:] | jnp.where(
                wordsel, acc_bits << j0, 0)
            accv = (jnp.full((_BS, 1), acc_bits, jnp.int32) >> rowid) & 1
            masked = jnp.where(accv != 0, rows8, 0)               # (_BS, W32)
            orred = masked[0:1]
            for j in range(1, _BS):                # OR-reduce (not max: these
                orred = orred | masked[j:j + 1]    # are packed words)
            removed_ref[:] = removed_ref[:] | orred
            kept_ref[0] = cnt

        return 0

    jax.lax.fori_loop(0, _BR // _BS, body, 0)


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


@partial(jax.jit, static_argnames=("max_out", "iou_thresh"))
def nms_pallas(boxes: jnp.ndarray, scores: jnp.ndarray, max_out: int,
               iou_thresh: float, valid: jnp.ndarray | None = None):
    """Drop-in replacement for ``ops.nms.nms_padded`` (same signature and
    return contract: (keep_idx (max_out,) i32, keep_mask (max_out,) bool),
    selection order score-descending given score-sorted input).

    vmap-safe: batched callers (the detector vmaps ``propose`` over images)
    hit a ``custom_vmap`` rule that lowers to ``lax.map`` over single-image
    kernel calls — Mosaic cannot lower auto-batched SMEM block specs (a
    squeezed leading dim violates the (8, 128) block-shape rule), and the
    sweep is sequential per image anyway.

    On non-TPU backends (the CPU test mesh) this delegates to the pure-JAX
    oracle — Mosaic kernels only lower on TPU.  A chip run must prove which
    side it took: ``chip_smoke.py`` fails unless the programs it ran hold
    the ``tpu_custom_call`` and the kernel equals the oracle on the chip
    (scripts/check_pallas.py is the longer hand-run sweep);
    tests/test_tpu_kernels.py compiles ``_nms_core`` for a described v5e.
    """
    if jax.default_backend() != "tpu":
        from mx_rcnn_tpu.ops.nms import nms_padded

        return nms_padded(boxes, scores, max_out=max_out,
                          iou_thresh=iou_thresh, valid=valid)
    n = boxes.shape[0]
    if valid is None:
        valid = jnp.ones((n,), bool)
    return _nms_vmappable(max_out, iou_thresh)(boxes, scores, valid)


def _nms_vmappable(max_out: int, iou_thresh: float):
    fn = _VMAP_CACHE.get((max_out, iou_thresh))
    if fn is not None:
        return fn

    @jax.custom_batching.custom_vmap
    def fn(boxes, scores, valid):
        return _nms_core(boxes, scores, valid, max_out, iou_thresh)

    @fn.def_vmap
    def _rule(axis_size, in_batched, boxes, scores, valid):
        boxes, scores, valid = (
            a if b else jnp.broadcast_to(a[None], (axis_size,) + a.shape)
            for a, b in zip((boxes, scores, valid), in_batched)
        )
        # The Mosaic kernels can't auto-batch (SMEM specs), so each batch
        # level becomes one serial lax.map (map_images; on a mesh, each
        # device over its own rows).  The map body calls the
        # custom_vmap-wrapped fn — NOT _nms_core — so a nested vmap batches
        # the inner call, re-enters this rule, and gets its own lax.map
        # instead of pushing batching into the pallas_call (the lowering
        # failure this rule exists to avoid).  Glue (prep/post) inside vs
        # outside the scan measured perf-neutral at B=8: the scan's
        # residual cost is kernel sequencing, not glue.
        out = map_images(fn, (boxes, scores, valid))
        return out, (True, True)

    _VMAP_CACHE[(max_out, iou_thresh)] = fn
    return fn


_VMAP_CACHE: dict = {}


def _nms_core(boxes: jnp.ndarray, scores: jnp.ndarray, valid: jnp.ndarray,
              max_out: int, iou_thresh: float):
    del scores  # selection order is index order (callers pass sorted boxes)
    n = boxes.shape[0]
    keep_words = _nms_kernels(*_nms_prep(boxes, valid, iou_thresh),
                              max_out=max_out, iou_thresh=iou_thresh)
    return _nms_post(keep_words, n=n, max_out=max_out)


def _nms_prep(boxes: jnp.ndarray, valid: jnp.ndarray, iou_thresh: float):
    """Host-of-kernel data prep (pure jnp, vmappable): pad, regroup column
    boxes for the bit-lane loop, pack the 8×8 block-diagonal + validity."""
    n = boxes.shape[0]
    n_pad = _pad_to(n, _PAD)   # (n_pad/_PL) lane-aligned, divisible by _BR
    w32 = n_pad // _PL

    boxes_p = jnp.zeros((n_pad, 4), jnp.float32).at[:n].set(
        boxes.astype(jnp.float32))
    valid_p = jnp.zeros((n_pad,), bool).at[:n].set(valid)

    # column boxes regrouped so bit-lane j of the pack loop reads columns
    # {32w + j} as a contiguous row: (4, W32, 32) -> (4, 32, W32)
    cols = boxes_p.T.reshape(4, w32, _PL).transpose(0, 2, 1)

    # 8x8 block-diagonal, bit-packed 2 words per block for SMEM scalar
    # loads: word k of block r, byte j' (col j = 4k + j'), bit i =
    # sup[8r+i, 8r+j].  Recomputed via boxes.bbox_overlaps: consistency is
    # structural — every same-block pair is decided solely by this table
    # and every cross-block pair solely by sup, so a ULP divergence
    # between the lowerings cannot produce contradictory decisions.
    from mx_rcnn_tpu.ops.boxes import bbox_overlaps

    gb = boxes_p.reshape(-1, _BS, 4)                     # (N/8, 8, 4)
    iou_blk = jax.vmap(bbox_overlaps)(gb, gb)            # (N/8, 8, 8) [i, j]
    dbits = (iou_blk > iou_thresh).astype(jnp.int32)
    rowsh = jnp.arange(_BS, dtype=jnp.int32)[None, :, None]   # bit i
    colgrp = jnp.sum(dbits << rowsh, axis=1)             # (N/8, 8) per-col j
    bytesh = (jnp.arange(_BS, dtype=jnp.int32) % 4) * 8  # byte within word
    packed = colgrp << bytesh[None, :]                   # (N/8, 8)
    diagp = jnp.stack([
        packed[:, 0] | packed[:, 1] | packed[:, 2] | packed[:, 3],
        packed[:, 4] | packed[:, 5] | packed[:, 6] | packed[:, 7],
    ], axis=1).reshape(-1)                               # (N/8 * 2,)

    # classic packing for valid: word w bit j = valid[32w + j]
    valid_words = jnp.sum(
        valid_p.astype(jnp.int32).reshape(w32, _PL) <<
        jnp.arange(_PL, dtype=jnp.int32)[None, :], axis=1).reshape(1, w32)
    return boxes_p, cols, diagp, valid_words


def _nms_kernels(boxes_p, cols, diagp, valid_words, *, max_out: int,
                 iou_thresh: float):
    """The two Mosaic kernels (phase A + sweep) — the only part the batched
    rule must run per-image under lax.map."""
    n_pad = boxes_p.shape[0]
    w32 = n_pad // _PL
    thresh = jnp.asarray([iou_thresh], jnp.float32)

    cw = 128                       # col-word tile: 128 lanes = 4096 columns
    sup = pl.pallas_call(
        _suppress_kernel,
        grid=(n_pad // _BR, w32 // cw),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((_BR, 4), lambda r, c: (r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_PL, cw), lambda r, c: (0, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_PL, cw), lambda r, c: (0, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_PL, cw), lambda r, c: (0, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_PL, cw), lambda r, c: (0, c),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_BR, cw), lambda r, c: (r, c),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_pad, w32), jnp.int32),
    )(thresh, boxes_p, cols[0], cols[1], cols[2], cols[3])

    keep_words = pl.pallas_call(
        _sweep_kernel,
        grid=(n_pad // _BR,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((_BR, w32), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, w32), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, w32), jnp.int32),
                        pltpu.SMEM((1,), jnp.int32)],
    )(jnp.asarray([max_out], jnp.int32), diagp, sup, valid_words)
    return keep_words


def _nms_post(keep_words, *, n: int, max_out: int):
    """Unpack the kept-bit words and compact to max_out slots (pure jnp,
    vmappable)."""
    n_pad = keep_words.shape[1] * _PL
    # unpack: word w bit j = column 32w + j, C-order reshape restores it
    keep_bits = ((keep_words[0][:, None] >>
                  jnp.arange(_PL, dtype=jnp.int32)[None, :]) & 1)
    keep_mask_full = keep_bits.reshape(n_pad)[:n] > 0
    # kept boxes in index order == score order; compact to max_out slots
    # (pad when n < max_out so the output shape contract always holds)
    order = jnp.argsort(jnp.where(keep_mask_full, 0, 1), stable=True)
    if n < max_out:
        pad = max_out - n
        keep_idx = jnp.concatenate(
            [order, jnp.zeros((pad,), order.dtype)]).astype(jnp.int32)
        keep_mask = jnp.concatenate(
            [keep_mask_full[order], jnp.zeros((pad,), bool)])
    else:
        keep_idx = order[:max_out].astype(jnp.int32)
        keep_mask = keep_mask_full[keep_idx]
    keep_idx = jnp.where(keep_mask, keep_idx, 0)
    return keep_idx, keep_mask
