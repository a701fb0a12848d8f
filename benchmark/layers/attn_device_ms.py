"""The attention kernel's device time a batch: over the device ops the
configuration's ``names.attn_kernel`` matches — one instruction a global
block, each run once an execution of the predict program — the sum of their
mean durations in the traced stretch.  A configuration that names no such
kernel, or a trace that holds none of its ops (a program without it, as the
parent's), gives None."""

import re


def per_batch_seconds(ctx):
    pattern = ctx["config"].get("names", {}).get("attn_kernel")
    if not pattern:
        return None
    rx = re.compile(pattern)
    hits = [(t, n) for name, (t, n) in ctx["trace"]["op_time"].items()
            if rx.search(name) and n > 0]
    total = sum(t / n for t, n in hits)
    return total if total > 0 else None


def read(ctx):
    s = per_batch_seconds(ctx)
    return None if s is None else 1e3 * s
