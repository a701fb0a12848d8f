"""Mean device duration of the RoI cascade's program — stages 2.. of a
Cascade R-CNN, pooling again at the boxes stage 1 refined — over its whole
executions in the traced stretch (the trace's "XLA Modules" line; the
configuration's ``names.roi_cascade_program``).  A configuration that names
no such program, or a trace that holds none of its executions (a program
without the stages), gives None."""

from benchmark import xplane


def read(ctx):
    pattern = ctx["config"].get("names", {}).get("roi_cascade_program")
    if not pattern:
        return None
    times = xplane.module_times(ctx["trace"], pattern)
    return 1e3 * sum(times) / len(times) if times else None
