"""The comparison that decides ``correct`` for a detection server.

Every record the timed path returned for a sampled request is held against
the plain reference's dense candidates for the same body (every RoI x every
class, before the per-class NMS and the cap, so the cut at the cap and the
order of near-ties cannot decide the comparison).  A served record of class
k is matched to the reference candidate of class k that overlaps its box
most; ``box_gap`` is 1 - IoU of the two, ``score_gap`` the difference of the
scores as a share of the larger.  Rounding moves both a little; a few
proposals flip in the RPN's NMS and then no candidate is near, so a response
is read by its **median** record and a cell by its **worst response**.  The
share of records with no candidate at IoU 0.5 is reported beside them.

What needs no reference is exact: records sorted by score; within a class no
two boxes over the NMS threshold (with a hundredth of slack for float32 IoU
at the edge); every score over the threshold.
"""

from __future__ import annotations

import statistics

import numpy as np

from benchmark.reference.frcnn_c4 import iou_one_many


def match_records(recs: list, prob: np.ndarray, boxes: np.ndarray):
    """-> [(box_gap, score_gap)] per served record."""
    out = []
    for r in recs:
        k = r["cls"]
        iou = iou_one_many(np.asarray(r["bbox"], np.float64),
                           boxes[:, 4 * k:4 * k + 4].astype(np.float64))
        near = np.flatnonzero(iou >= iou.max() - 0.02)
        p = prob[near, k]
        j = int(np.argmin(np.abs(p - r["score"])))
        out.append((1.0 - float(iou[near[j]]),
                    abs(float(p[j]) - r["score"])
                    / max(float(p[j]), r["score"], 1e-12)))
    return out


def structure_faults(recs: list, nms_thresh: float, score_thresh: float):
    """Counts of what a record list may never show."""
    order = sum(1 for a, b in zip(recs, recs[1:]) if a["score"] < b["score"])
    low = sum(1 for r in recs if not r["score"] > score_thresh)
    overlap = 0
    by_cls: dict = {}
    for r in recs:
        by_cls.setdefault(r["cls"], []).append(np.asarray(r["bbox"]))
    for bxs in by_cls.values():
        arr = np.stack(bxs).astype(np.float64)
        for i in range(len(arr) - 1):
            overlap += int((iou_one_many(arr[i], arr[i + 1:])
                            > nms_thresh + 0.01).sum())
    return order, low, overlap


def compare(sample: list, dense: list, net: dict) -> dict:
    """sample[i]["detections"] against dense[i] = (prob, boxes) -> the
    numbers compared, by name."""
    box_med, score_med, far, total = [], [], 0, 0
    order = low = overlap = 0
    for s, (prob, boxes) in zip(sample, dense):
        recs = s["detections"]
        o, l, v = structure_faults(recs, net["test_nms"], net["test_thresh"])
        order, low, overlap = order + o, low + l, overlap + v
        if not recs:
            continue
        gaps = match_records(recs, prob, boxes)
        box_med.append(statistics.median(g[0] for g in gaps))
        score_med.append(statistics.median(g[1] for g in gaps))
        far += sum(1 for g in gaps if g[0] > 0.5)
        total += len(gaps)
    return {
        "records": float(total),
        "box_gap": max(box_med) if box_med else 1.0,
        "score_gap": max(score_med) if score_med else 1.0,
        "far_share": far / total if total else 1.0,
        "order_faults": float(order), "low_scores": float(low),
        "nms_faults": float(overlap),
    }


def judge(numbers: dict, limits: dict) -> tuple:
    """-> (correct, {name: (value, limit)}).  ``records`` has a lower limit
    (a sample with nothing in it proves nothing); the rest upper limits."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers[name]
        compared[name] = (value, limit)
        good = value >= limit if name == "records" else value <= limit
        ok = ok and bool(good)
    return ok, compared
