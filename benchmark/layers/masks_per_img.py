"""Records answered with a mask, an image served in the window: the
engine's ``mask_rois`` counter (serve/engine.py ``_mask_stage``, a mask
network only) over ``served``.  The paste's and the response's real work
scale with it; ``TEST.MAX_PER_IMAGE`` caps it.  A program without the
counter gives None."""


def read(ctx):
    a = ctx["metrics_after"].get("counters") or {}
    b = ctx["metrics_before"].get("counters") or {}
    if a.get("mask_rois") is None or b.get("mask_rois") is None:
        return None
    served = a.get("served", 0) - b.get("served", 0)
    if served <= 0:
        return None
    return (a["mask_rois"] - b["mask_rois"]) / served
