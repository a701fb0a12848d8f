"""The comparison that decides ``correct`` for a server that answers a mask
with every record: the ``compare`` row of ``benchmark/README.md``, "A
configuration", for ``r101-fpn-mask``.

The box half is ``benchmark.compare``'s, unchanged in name and meaning
(``records``, ``box_gap``, ``score_gap``, ``far_share``, ``order_faults``,
``low_scores``, ``nms_faults``): every served record against the
reference's dense candidates.

The mask half holds every served ``segmentation`` to the plain reference's
mask branch run on the **served record's own box and class**
(``reference.mrcnn_fpn.masks``): the branch is a function of the final box,
so this compares each mask where it was computed, and a box that is off is
``box_gap``'s to find.

* ``mask_missing``: records whose ``segmentation`` is absent, whose ``size``
  is not the request's ``[h, w]``, whose counts do not sum to h * w, or that
  set a pixel outside their paste window [floor x1, ceil x2] x [floor y1,
  ceil y2].  Exact: the limit is 0.
* ``mask_gap``: 1 - IoU of the served mask with the reference's map cut at
  0.5 (two empty masks: 0).  Rounding flips pixels on the 0.5 contour, so a
  response is read by its **median** record and a cell by its **worst
  response**, as the box gaps are.
* ``mask_firm_faults``: the share of window pixels (all sampled records
  together) whose served bit differs from the reference's **although** the
  reference's probability is further than ``net["mask_margin"]`` from 0.5:
  what rounding cannot explain.
* ``mask_fill``: the mean share of set pixels inside the paste window.  A
  value outside its range (``correct["mask_fill"]`` = [low, high]) means
  that the weights, not the program, decide the comparison: masks that are
  all empty or all full agree whatever computed them.
* ``masks``: how many served masks were compared.
"""

from __future__ import annotations

import statistics

import numpy as np

from benchmark import compare as boxes_compare
from benchmark.reference import mrcnn_fpn


def decode_counts(counts, h: int, w: int):
    """COCO's uncompressed column-major RLE -> (h, w) bool, or None where
    the counts are no whole numbers or do not sum to h * w."""
    try:
        runs = np.asarray(counts, np.int64)
    except (TypeError, ValueError, OverflowError):
        return None
    if runs.ndim != 1 or (runs < 0).any() or int(runs.sum()) != h * w:
        return None
    bits = np.repeat(np.arange(len(runs)) % 2 == 1, runs)
    return bits.reshape((w, h)).T


def window_of(box, h: int, w: int):
    """The visible paste window of a box: (x0, y0, x1, y1), upper edges
    exclusive."""
    x0, y0 = max(int(np.floor(box[0])), 0), max(int(np.floor(box[1])), 0)
    x1 = min(int(np.ceil(box[2])), w - 1) + 1
    y1 = min(int(np.ceil(box[3])), h - 1) + 1
    return x0, y0, max(x1, x0), max(y1, y0)


def served_mask(rec: dict, h: int, w: int):
    """One record's mask inside its window -> (rows, columns) bool, or None
    where the record has no well-formed mask or sets a pixel outside."""
    seg = rec.get("segmentation")
    if not isinstance(seg, dict) or seg.get("size") != [h, w]:
        return None
    bits = decode_counts(seg.get("counts"), h, w)
    if bits is None:
        return None
    x0, y0, x1, y1 = window_of(rec["bbox"], h, w)
    inside = bits[y0:y1, x0:x1]
    if int(bits.sum()) != int(inside.sum()):
        return None
    return inside


def compare(sample: list, dense: list, net: dict) -> dict:
    """sample[i]["detections"] (records with ``segmentation``) against
    dense[i] (``reference.mrcnn_fpn.detect``'s document) -> the numbers
    compared, by name."""
    out = boxes_compare.compare(
        sample, [(d["prob"], d["boxes"]) for d in dense], net)
    margin = net["mask_margin"]
    missing = compared = 0
    firm_faults = pixels = 0
    gap_med, fills = [], []
    for s, d in zip(sample, dense):
        recs = s["detections"]
        h, w = d["hw"]
        if not recs:
            continue
        ref = mrcnn_fpn.masks(d, np.asarray([r["bbox"] for r in recs]),
                              [r["cls"] for r in recs], net)
        gaps = []
        for rec, (_origin, prob) in zip(recs, ref):
            got = served_mask(rec, h, w)
            if got is None or got.shape != prob.shape:
                missing += 1
                continue
            want = prob >= 0.5
            union = int((got | want).sum())
            gaps.append(1.0 - int((got & want).sum()) / union
                        if union else 0.0)
            firm = np.abs(prob - 0.5) > margin
            firm_faults += int(((got != want) & firm).sum())
            pixels += got.size
            if got.size:
                fills.append(float(got.mean()))
            compared += 1
        if gaps:
            gap_med.append(statistics.median(gaps))
    out.update({
        "masks": float(compared),
        "mask_missing": float(missing),
        "mask_gap": max(gap_med) if gap_med else 1.0,
        "mask_firm_faults": firm_faults / pixels if pixels else 1.0,
        "mask_fill": statistics.fmean(fills) if fills else 0.0,
    })
    return out


def judge(numbers: dict, limits: dict) -> tuple:
    """-> (correct, {name: (value, limit)}).  ``records`` and ``masks`` have
    lower limits (a sample with nothing in it proves nothing), ``mask_fill``
    a range [low, high]; the rest upper limits."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers[name]
        compared[name] = (value, limit)
        if name in ("records", "masks"):
            good = value >= limit
        elif name == "mask_fill":
            good = limit[0] <= value <= limit[1]
        else:
            good = value <= limit
        ok = ok and bool(good)
    return ok, compared
