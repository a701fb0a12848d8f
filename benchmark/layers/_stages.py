"""What the stage readers share.  The server's ``/metrics`` carries, for
every stage it times (``telemetry.stage``), a monotone ``{"count", "sum_s"}``
under ``"stages"``: the difference of the two snapshots is the window's.  A
program without them (the parent of the PR that brought them) gives None."""


def delta(ctx, name):
    """(count, seconds) of one stage inside the window, or None."""
    after = (ctx["metrics_after"].get("stages") or {}).get(name)
    before = (ctx["metrics_before"].get("stages") or {}).get(name)
    if after is None or before is None:
        return None
    return after["count"] - before["count"], after["sum_s"] - before["sum_s"]


def mean_ms(ctx, *names):
    """Milliseconds an observation, the named stages one after another
    (each stage's window seconds over its own window count), or None."""
    total = 0.0
    for name in names:
        d = delta(ctx, name)
        if d is None or d[0] <= 0:
            return None
        total += d[1] / d[0]
    return 1e3 * total


def compile_counter(doc, name):
    """One of the registry's counters in a ``/metrics`` document, or None."""
    return ((doc.get("compile") or {}).get("counters") or {}).get(name)
