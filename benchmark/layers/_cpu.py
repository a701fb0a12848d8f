"""What the CPU readers share.  Since PR 37 every ``/metrics["stages"]``
entry also carries ``cpu_s`` and ``minflt``: the CPU seconds and minor page
faults of the thread that observed it, between the same two instants as its
``sum_s`` (``telemetry.stage``; ``serve/service_time``: the dispatcher's, from
one claim to the next).  ``/metrics["host"]`` carries the whole process's CPU
seconds (``cpu_s``) and the cores it may run on (``cores``).  A program
without them (the parent of that PR) gives None."""


def per_use(ctx, names, key, per):
    """The window's sum of ``key`` over the stages ``names``, over the
    window's count of the stage ``per``; None where a stage or the key is
    missing, or ``per`` never ran in the window."""
    after = ctx["metrics_after"].get("stages") or {}
    before = ctx["metrics_before"].get("stages") or {}
    if any(key not in (after.get(n) or {}) or key not in (before.get(n) or {})
           for n in names) or per not in after or per not in before:
        return None
    count = after[per]["count"] - before[per]["count"]
    if count <= 0:
        return None
    return sum(after[n][key] - before[n][key] for n in names) / count


def host(ctx):
    """(the process's CPU seconds, the server's seconds) between the two
    snapshots, or None."""
    after, before = ctx["metrics_after"], ctx["metrics_before"]
    if "host" not in after or "host" not in before:
        return None
    return (after["host"]["cpu_s"] - before["host"]["cpu_s"],
            after["t_s"] - before["t_s"])
