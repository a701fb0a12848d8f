"""The whole predict step's share of the chip's bf16 peak: the FLOPs one
batch requires (from shapes, ``benchmark/flops.py``; the batch as
dispatched, padding included — ``batch_fill`` says how much of it was
requests) over the program's mean device time."""

from benchmark import xplane


def read(ctx):
    times = xplane.module_times(ctx["trace"],
                                ctx["config"]["names"]["predict_program"])
    if not times:
        return None
    work = (ctx["flops"].predict_flops_per_image(ctx["config"]["net"])["total"]
            * ctx["config"]["batch_per_chip"])
    mean_s = sum(times) / len(times)
    return 100.0 * work / (mean_s * ctx["peaks"]["bf16_flops_per_s"])
