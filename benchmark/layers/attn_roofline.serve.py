"""The attention kernel's share of its roofline: the least time the chip
could take for one image's global attention (``flops.attn_work``: the
mathematics' operations over the bf16 peak or its bytes over the HBM peak,
whichever is larger) over the kernel's device time an image (its time a
batch, as ``attn_device_ms`` reads it, over the batch as dispatched).  What
the kernel spends beyond the mathematics — the relative terms folded into
longer rows, exponentials, block padding — reads as a lower share; never 0,
never clipped.  None where the configuration names no kernel, its ``flops``
module has no ``attn_work``, or the trace holds none of its ops."""

from benchmark.layers import attn_device_ms as _attn


def read(ctx):
    work_of = getattr(ctx["flops"], "attn_work", None)
    per_batch_s = _attn.per_batch_seconds(ctx)
    if work_of is None or per_batch_s is None:
        return None
    work = work_of(ctx["config"]["net"])
    least_s, _bound = ctx["flops"].roofline_seconds(work["ops"], work["bytes"],
                                                    ctx["peaks"])
    return 100.0 * least_s * ctx["config"]["batch_per_chip"] / per_batch_s
