"""The comparison that decides ``correct`` for ``vitdet-b-mask``: the
``compare`` row of ``benchmark/README.md``, "A configuration".

``benchmark.mask.compare``'s numbers, limits and judgement, name for name
(its docstring says what each means).  That module calls
``reference.mrcnn_fpn.masks`` by name, whose mask head has no LayerNorm, so
it cannot be named in this configuration's ``modules`` as it is: the one
thing that differs here is which plain reference computes the mask at a
served record's box — ``reference.mrcnn_vitdet.masks``.  The box half is
``benchmark.compare``'s and every helper (``served_mask``, ``judge``) is
``benchmark.mask.compare``'s, unchanged.
"""

from __future__ import annotations

import statistics

import numpy as np

from benchmark import compare as boxes_compare
from benchmark.mask.compare import judge, served_mask  # noqa: F401
from benchmark.reference import mrcnn_vitdet


def compare(sample: list, dense: list, net: dict) -> dict:
    """sample[i]["detections"] (records with ``segmentation``) against
    dense[i] (``reference.mrcnn_vitdet.detect``'s document) -> the numbers
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
        ref = mrcnn_vitdet.masks(d, np.asarray([r["bbox"] for r in recs]),
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
