"""Boxes over ``TEST.THRESH`` that went into the per-class NMS, an image
served in the window: the engine's ``post_candidates`` counter (counted in
``serve/engine.py::_forward_legacy``) over ``served``.  The host post-process's
work scales with it."""


def read(ctx):
    a = ctx["metrics_after"].get("counters") or {}
    b = ctx["metrics_before"].get("counters") or {}
    if a.get("post_candidates") is None or b.get("post_candidates") is None:
        return None
    served = a.get("served", 0) - b.get("served", 0)
    if served <= 0:
        return None
    return (a["post_candidates"] - b["post_candidates"]) / served
