"""The RoI cascade's program's share of the chip's bf16 peak: the FLOPs its
stages require a batch (the configuration's ``flops.roi_cascade_work``: the
later stages' box heads on every RoI and their RoIAlign on one level a RoI,
times the batch as dispatched) over the program's mean device time.  What
the program spends beyond that — pooling every RoI on all four levels'
whole maps — is not credited, so it reads low by what the program wastes
and cannot read over 100 %.  None — never 0 — where nothing was traced or
the configuration counts no such work."""

from benchmark.layers import roi_cascade_device_ms as _device


def read(ctx):
    work_of = getattr(ctx["flops"], "roi_cascade_work", None)
    ms = _device.read(ctx)
    if work_of is None or not ms:
        return None
    work = work_of(ctx["config"]["net"])["total"] \
        * ctx["config"]["batch_per_chip"]
    return 100.0 * work / (1e-3 * ms * ctx["peaks"]["bf16_flops_per_s"])
