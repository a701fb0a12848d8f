"""Proposals that survived the joint NMS, an image served in the window:
the engine's ``rois_valid`` counter (counted on the host in
``serve/engine.py::_forward_legacy`` from the ``roi_valid`` it reads back,
on a pyramid network only) over ``served``.  The RoI head's and the host
post-process's real work scale with it; the rest of the 1000 rows is
padding.  A program without the counter (the parent of the PR that brought
it, a single-level network) gives None."""


def read(ctx):
    a = ctx["metrics_after"].get("counters") or {}
    b = ctx["metrics_before"].get("counters") or {}
    if a.get("rois_valid") is None or b.get("rois_valid") is None:
        return None
    served = a.get("served", 0) - b.get("served", 0)
    if served <= 0:
        return None
    return (a["rois_valid"] - b["rois_valid"]) / served
