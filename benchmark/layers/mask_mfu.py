"""The mask program's share of the chip's bf16 peak: the FLOPs the mask
head requires for the records a dispatch really masked (the configuration's
``flops.mask_flops_per_image`` a RoI x the engine's ``mask_rois`` over
``mask_dispatches`` in the window) over the program's mean device time.
Padding slots and a pooling that contracts over whole maps are not
credited, so it reads low by what the program wastes and cannot read over
100 %.  None — never 0 — where nothing was traced, the counters are absent
or no record was masked."""

from benchmark import xplane


def read(ctx):
    pattern = ctx["config"].get("names", {}).get("mask_program")
    per_roi = getattr(ctx["flops"], "mask_flops_per_image", None)
    if not pattern or per_roi is None:
        return None
    times = xplane.module_times(ctx["trace"], pattern)
    a = ctx["metrics_after"].get("counters") or {}
    b = ctx["metrics_before"].get("counters") or {}
    if not times or any(d.get(k) is None for d in (a, b)
                        for k in ("mask_rois", "mask_dispatches")):
        return None
    dispatches = a["mask_dispatches"] - b["mask_dispatches"]
    rois = a["mask_rois"] - b["mask_rois"]
    if dispatches <= 0 or rois <= 0:
        return None
    work = per_roi(ctx["config"]["net"], rois / dispatches)
    mean_s = sum(times) / len(times)
    return 100.0 * work / (mean_s * ctx["peaks"]["bf16_flops_per_s"])
