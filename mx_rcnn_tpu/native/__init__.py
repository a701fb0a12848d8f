"""ctypes bindings for the native CPU eval kernels (src/mxr_native.cpp).

Mirrors the reference's native tier (``rcnn/cython`` + pycocotools C): IoU
matrix, greedy NMS, RLE intersection/IoU.  The library is built on first
use (``make`` → g++, ~1 s) and rebuilt when ``src/mxr_native.cpp`` is newer
than the ``.so`` (its mtime), so an entry point added to the source is in
the library the next process loads; every entry point has a pure-numpy
fallback, so an unbuildable environment degrades to slower eval, never to
failure.

API (drop-in with the numpy versions):
  bbox_overlaps(boxes (N,4), query (K,4)) -> (N,K) f32
  nms(dets (N,5), thresh) -> list[int]
  nms_classes(scores (R,K), boxes (R,4K) or (R,4), valid (R,), thresh,
      nms_thresh) -> (N,6) f32 [x1,y1,x2,y2,score,cls] or None   (one
      image's per-class NMS in ONE foreign call; the caller's fallback is a
      loop over ``nms``)
  rle_iou(dts, gts, iscrowd) -> (D,G) f64   (RLE dicts, uncompressed counts)
  available(symbol=None) -> bool
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional

import numpy as np

from mx_rcnn_tpu.logger import logger

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libmxr_native.so")
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    src = os.path.join(_DIR, "src", "mxr_native.cpp")
    stale = (not os.path.exists(_SO)
             or (os.path.exists(src)
                 and os.path.getmtime(src) > os.path.getmtime(_SO)))
    if stale:
        try:
            subprocess.run(["make", "-C", _DIR], check=True,
                           capture_output=True, timeout=120)
        except Exception as e:  # no toolchain → numpy fallback
            if not os.path.exists(_SO):
                logger.warning("native build failed (%s); using numpy "
                               "fallbacks", e)
                return None
            logger.warning("native rebuild failed (%s); using the stale "
                           "library", e)
    try:
        lib = ctypes.CDLL(_SO)
    except OSError as e:
        logger.warning("native load failed (%s); using numpy fallbacks", e)
        return None

    lib.mxr_bbox_overlaps.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float)]
    lib.mxr_nms.restype = ctypes.c_int64
    lib.mxr_nms.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64, ctypes.c_float,
        ctypes.POINTER(ctypes.c_int64)]
    for name in ("mxr_nms_classes", "mxr_nms_classes_shared"):
        try:  # absent only in a stale .so that failed to rebuild
            fn = getattr(lib, name)
        except AttributeError:
            logger.warning("stale native library has no %s; per_class_nms "
                           "loops over the classes there", name)
            continue
        fn.restype = ctypes.c_int64
        fn.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.POINTER(ctypes.c_float)]
    try:  # absent only in a stale pre-round-4 .so that failed to rebuild
        lib.mxr_rle_encode.restype = ctypes.c_int64
        lib.mxr_rle_encode.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_uint32)]
        lib.mxr_paste_rle.restype = ctypes.c_int64
        lib.mxr_paste_rle.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
            ctypes.c_int64, ctypes.c_int64, ctypes.POINTER(ctypes.c_uint32)]
    except AttributeError:
        logger.warning("stale native library has no mask RLE entry points; "
                       "mask eval uses the host fallbacks")
    lib.mxr_rle_iou.argtypes = [
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_double)]
    _lib = lib
    return _lib


def available(symbol: Optional[str] = None) -> bool:
    """Whether the library loaded — and, given ``symbol``, has that entry
    point (a stale ``.so`` that could not be rebuilt may lack a newer one)."""
    lib = _load()
    return lib is not None and (symbol is None or hasattr(lib, symbol))


def _fptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def bbox_overlaps(boxes: np.ndarray, query: np.ndarray) -> np.ndarray:
    lib = _load()
    boxes = np.ascontiguousarray(boxes, np.float32)
    query = np.ascontiguousarray(query, np.float32)
    if lib is None:
        from mx_rcnn_tpu.ops.boxes import bbox_overlaps as jb

        return np.asarray(jb(boxes, query))
    n, k = len(boxes), len(query)
    out = np.empty((n, k), np.float32)
    lib.mxr_bbox_overlaps(_fptr(boxes), n, _fptr(query), k, _fptr(out))
    return out


def nms(dets: np.ndarray, thresh: float) -> List[int]:
    lib = _load()
    if lib is None or len(dets) == 0:
        from mx_rcnn_tpu.ops.nms import nms as py_nms

        return py_nms(np.asarray(dets, np.float32), thresh)
    dets = np.ascontiguousarray(dets, np.float32)
    keep = np.empty(len(dets), np.int64)
    cnt = lib.mxr_nms(_fptr(dets), len(dets), thresh,
                      keep.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    return keep[:cnt].tolist()


def nms_classes(scores: np.ndarray, boxes: np.ndarray, valid,
                thresh: float, nms_thresh: float) -> Optional[np.ndarray]:
    """One image's per-class NMS in one foreign call: (R, K) scores,
    (R, 4K) boxes — or (R, 4), one a row for every class
    (``mxr_nms_classes_shared``) —, (R,) validity → the kept rows ``[x1,
    y1, x2, y2, score, cls]`` as (N, 6) float32, classes 1..K-1 in turn,
    each in the order :func:`nms` keeps — or None when the library (or this
    entry point) is missing and the caller has to loop.

    Float32 throughout, as the loop over :func:`nms` computes: scores and
    boxes of another dtype are cast first, so a score is compared with
    ``thresh`` as the float32 it is kept as."""
    lib = _load()
    scores = np.ascontiguousarray(scores, np.float32)
    boxes = np.ascontiguousarray(boxes, np.float32)
    shared = boxes.shape[1:] == (4,)
    name = "mxr_nms_classes_shared" if shared else "mxr_nms_classes"
    if lib is None or not hasattr(lib, name):
        return None
    valid = np.ascontiguousarray(valid, bool)
    assert scores.ndim == 2 and valid.shape == scores.shape[:1] \
        and boxes.shape[0] == scores.shape[0] \
        and (shared or boxes.shape[1] == 4 * scores.shape[1]), \
        f"(R, K) scores, (R, 4K) boxes (or (R, 4): one a row) and (R,) " \
        f"valid wanted; got {scores.shape}, {boxes.shape}, {valid.shape}"
    r, k = scores.shape
    out = np.empty((r * max(k - 1, 0), 6), np.float32)
    n = getattr(lib, name)(
        _fptr(scores), _fptr(boxes),
        valid.view(np.uint8).ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        r, k, thresh, nms_thresh, _fptr(out))
    return out[:n]


_enc = threading.local()  # .buf: reused across ONE thread's encode calls


def _enc_buf(need: int) -> np.ndarray:
    """This thread's count buffer, at least ``need`` long.  ctypes lets go
    of the GIL for the foreign call, so a buffer shared between threads is
    written by two encoders at once (ROADMAP D0: 3,117 of 6,400 RLEs wrong
    from two threads); a server answers from more than one."""
    buf = getattr(_enc, "buf", None)
    if buf is None or buf.size < need:
        buf = _enc.buf = np.empty(need, np.uint32)
    return buf


def rle_encode_packed(packed: np.ndarray, h: int, w: int) -> List[int]:
    """Bit-packed transposed mask (Wp, Hp//8) uint8 (ops/mask_paste.py
    layout) → column-major COCO RLE counts over the true (h, w) frame.

    The C++ encoder streams each column as 64-bit words (the packed layout
    puts column y-runs in sequential bytes); the numpy fallback unpacks the
    bits and reuses the oracle encoder — identical counts either way.
    """
    packed = np.ascontiguousarray(packed, np.uint8)
    hp = packed.shape[1] * 8
    assert hp % 64 == 0, \
        f"packed height {hp} must be a multiple of 64 (C++ word streaming)"
    assert h <= hp and w <= packed.shape[0], \
        f"frame ({h}, {w}) exceeds packed capacity ({hp}, {packed.shape[0]})"
    lib = _load()
    if lib is None or not hasattr(lib, "mxr_rle_encode"):
        from mx_rcnn_tpu.eval import mask_rle

        mask = np.unpackbits(packed[:w], axis=-1, bitorder="little")
        return mask_rle.encode(mask[:, :h].T)["counts"]
    buf = _enc_buf(h * w + 1)
    n = lib.mxr_rle_encode(
        packed.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), hp, h, w,
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
    return buf[:n].tolist()


def paste_rle(prob: np.ndarray, box: np.ndarray, h: int, w: int):
    """(M, M) mask probabilities + original-frame box → full-frame
    column-major RLE counts, or None when the native library is missing
    (caller falls back to the cv2 paste_mask oracle).

    Fused C++ paste+RLE: separable bilinear resize streamed column by
    column with bulk zero spans outside the box — ~10-25 ms/img at the
    100-detection worst case vs ~150 ms for per-detection cv2 paste, and
    it only needs the 28×28 probabilities shipped from the device."""
    lib = _load()
    if lib is None or not hasattr(lib, "mxr_paste_rle"):
        return None
    prob = np.ascontiguousarray(prob, np.float32)
    buf = _enc_buf(h * w + 1)
    n = lib.mxr_paste_rle(
        _fptr(prob), prob.shape[0],
        float(box[0]), float(box[1]), float(box[2]), float(box[3]), h, w,
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
    return buf[:n].tolist()


def _flatten_counts(rles: list):
    counts = [np.asarray(r["counts"], np.uint32) for r in rles]
    off = np.zeros(len(rles) + 1, np.int64)
    for i, c in enumerate(counts):
        off[i + 1] = off[i] + len(c)
    flat = (np.concatenate(counts) if counts else np.zeros(0, np.uint32))
    return np.ascontiguousarray(flat), off


def rle_iou(dts: list, gts: list, iscrowd: np.ndarray) -> np.ndarray:
    lib = _load()
    if lib is None:
        from mx_rcnn_tpu.eval import mask_rle

        return mask_rle.rle_iou(dts, gts, np.asarray(iscrowd, bool))
    D, G = len(dts), len(gts)
    out = np.zeros((D, G), np.float64)
    if D == 0 or G == 0:
        return out
    n = int(dts[0]["size"][0]) * int(dts[0]["size"][1])
    dc, doff = _flatten_counts(dts)
    gc, goff = _flatten_counts(gts)
    d_area = np.asarray([int(np.sum(np.asarray(r["counts"])[1::2]))
                         for r in dts], np.int64)
    g_area = np.asarray([int(np.sum(np.asarray(r["counts"])[1::2]))
                         for r in gts], np.int64)
    crowd = np.ascontiguousarray(np.asarray(iscrowd, np.uint8))
    lib.mxr_rle_iou(
        dc.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        doff.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), D,
        gc.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        goff.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), G,
        d_area.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        g_area.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        crowd.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    return out
