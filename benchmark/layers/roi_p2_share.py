"""The share of the window's valid proposals that Lin et al.'s eq. 1 sends
to P2, the level whose map is largest (200 x 336 of the 800 x 1344 bucket):
the engine's ``rois_level_p2`` over ``rois_valid`` (both counted on the host
in ``serve/engine.py::_forward_legacy``, on a pyramid network only).  What a
pooling that touches only the assigned level would read most.  A program
without the counters gives None."""


def read(ctx):
    a = ctx["metrics_after"].get("counters") or {}
    b = ctx["metrics_before"].get("counters") or {}
    if any(c.get(k) is None for c in (a, b)
           for k in ("rois_valid", "rois_level_p2")):
        return None
    valid = a["rois_valid"] - b["rois_valid"]
    if valid <= 0:
        return None
    return 100.0 * (a["rois_level_p2"] - b["rois_level_p2"]) / valid
