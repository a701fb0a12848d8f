"""Requests served / (batches x serve batch) over the window, from the
engine's ``/metrics`` counters: the share of each dispatched batch that was
real requests, the rest being padding the device computes all the same."""


def read(ctx):
    a, b = ctx["metrics_after"]["counters"], ctx["metrics_before"]["counters"]
    batches = a["batches"] - b["batches"]
    if batches <= 0:
        return None
    size = ctx["metrics_after"]["options"]["batch_size"]
    return 100.0 * (a["served"] - b["served"]) / (batches * size)
