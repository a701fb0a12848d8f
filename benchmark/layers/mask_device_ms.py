"""Mean device duration of the mask program's whole executions in the
traced stretch (the trace's "XLA Modules" line; the configuration's
``names.mask_program``).  A configuration that names no such program, or a
trace that holds none of its executions, gives None."""

from benchmark import xplane


def read(ctx):
    pattern = ctx["config"].get("names", {}).get("mask_program")
    if not pattern:
        return None
    times = xplane.module_times(ctx["trace"], pattern)
    return 1e3 * sum(times) / len(times) if times else None
