"""Seconds XLA spent building or loading programs before the window opened
(``compile.counters.xla_compile_s`` at the first ``/metrics`` snapshot): the
part of set-up a warm persistent cache shortens."""

from benchmark.layers import _stages


def read(ctx):
    return _stages.compile_counter(ctx["metrics_before"], "xla_compile_s")
