"""The comparison that decides ``correct`` for ``r101-fpn-cascade-mask``: the
``compare`` row of ``benchmark/README.md``, "A configuration".

``benchmark.mask.compare``'s numbers, name for name (its docstring says
what each means), over ``reference.cmrcnn_fpn.detect``'s document: every
served record against the reference's dense candidates — each RoI's final
box b_4, the same for every class, scored by the stages' averaged
probabilities — and every served mask against the reference's mask branch
at the record's own box.  Those hold the answer to the reference; two more
numbers hold it to the **cascade**, so that a program which skips a stage
or runs one twice cannot pass on boxes and scores that happen to lie near:

* ``stage1_box_iou``: each served record's IoU with its matched RoI's box
  b_2 — stage 1's boxes once decoded, before stages 2 and 3 moved them; a
  response by its median record, the cell by its **highest** response.  A
  program that answered b_2 reads about 1; the cascade reads well under it
  (upper limit).
* ``stage1_score_gap``: each served score's difference from stage 1's
  probability alone for its matched RoI and class, as a share of the
  larger; a response by its median record, the cell by its **lowest**
  response.  A program that answered stage 1's scores reads its rounding
  (``score_gap``'s size); the average of three stages reads far above it
  (lower limit).

A served record is matched as ``benchmark.compare`` matches it: among the
reference's candidates of its class within 0.02 of the best IoU, the one
whose score is nearest.
"""

from __future__ import annotations

import statistics

import numpy as np

from benchmark.mask import compare as mask_compare
from benchmark.reference.frcnn_c4 import iou_one_many

LOWER = ("records", "masks", "stage1_score_gap")


def stage_numbers(recs: list, dense_doc: dict):
    """-> [(IoU with b_2, score gap to stage 1)] per served record."""
    box = dense_doc["box"].astype(np.float64)
    prob, s1 = dense_doc["prob"], dense_doc["prob_s1"]
    out = []
    for r in recs:
        k = r["cls"]
        served = np.asarray(r["bbox"], np.float64)
        iou = iou_one_many(served, box)
        near = np.flatnonzero(iou >= iou.max() - 0.02)
        j = int(near[np.argmin(np.abs(prob[near, k] - r["score"]))])
        first = float(s1[j, k])
        out.append((float(iou_one_many(served, dense_doc["box_s2"][j:j + 1]
                                       .astype(np.float64))[0]),
                    abs(first - r["score"]) / max(first, r["score"], 1e-12)))
    return out


def compare(sample: list, dense: list, net: dict) -> dict:
    """sample[i]["detections"] (records with ``segmentation``) against
    dense[i] (``reference.cmrcnn_fpn.detect``'s document) -> the numbers
    compared, by name."""
    out = mask_compare.compare(sample, dense, net)
    ious, gaps = [], []
    for s, d in zip(sample, dense):
        if not s["detections"]:
            continue
        got = stage_numbers(s["detections"], d)
        ious.append(statistics.median(g[0] for g in got))
        gaps.append(statistics.median(g[1] for g in got))
    out.update(stage1_box_iou=max(ious) if ious else 1.0,
               stage1_score_gap=min(gaps) if gaps else 0.0)
    return out


def judge(numbers: dict, limits: dict) -> tuple:
    """-> (correct, {name: (value, limit)}).  ``records``, ``masks`` and
    ``stage1_score_gap`` have lower limits, ``mask_fill`` a range [low,
    high]; the rest upper limits."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers[name]
        compared[name] = (value, limit)
        if name in LOWER:
            good = value >= limit
        elif name == "mask_fill":
            good = limit[0] <= value <= limit[1]
        else:
            good = value <= limit
        ok = ok and bool(good)
    return ok, compared
