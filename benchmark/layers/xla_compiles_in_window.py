"""Programs XLA built or loaded between the two ``/metrics`` snapshots, as
jax itself reports them (``compile.counters.xla_compiles``, from
compile/registry.py's ``jax.monitoring`` listeners): every program of the
process, the eager one-op ones too.  Nothing may compile in the window."""

from benchmark.layers import _stages


def read(ctx):
    after = _stages.compile_counter(ctx["metrics_after"], "xla_compiles")
    before = _stages.compile_counter(ctx["metrics_before"], "xla_compiles")
    if after is None or before is None:
        return None
    return after - before
