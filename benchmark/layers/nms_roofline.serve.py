"""The proposal NMS kernel's share of its roofline at the test contract
(6000 -> 300): the least time the chip could take for the work greedy NMS
requires (``flops.nms_work``; the larger of operations over the bf16 peak
and bytes over the HBM peak) over the kernel's mean device time per image.
The kernel is vector work, so against the MXU's peak it reads low."""

import re


def read(ctx):
    rx = re.compile(ctx["config"]["names"]["nms_kernel"])
    hits = [(t, n) for name, (t, n) in ctx["trace"]["op_time"].items()
            if rx.search(name) and n > 0]
    if not hits:
        return None
    # the program runs each of the kernel's calls once an image: one
    # image's kernel time is the sum of the calls' mean durations
    per_image_s = sum(t / n for t, n in hits)
    if per_image_s <= 0:
        return None
    net = ctx["config"]["net"]
    work = ctx["flops"].nms_work(net["test_pre_nms"], net["test_post_nms"])
    least_s, _bound = ctx["flops"].roofline_seconds(work["ops"], work["bytes"],
                                                    ctx["peaks"])
    return 100.0 * least_s / per_image_s
