"""Mean device duration of the predict program's whole executions in the
traced stretch (the trace's "XLA Modules" line)."""

from benchmark import xplane


def read(ctx):
    times = xplane.module_times(ctx["trace"],
                                ctx["config"]["names"]["predict_program"])
    return 1e3 * sum(times) / len(times) if times else None
