"""Mean length of a dispatcher turn in the window: the engine's own
``serve/service_time`` (batch taken off the queue -> its last response set),
window seconds over window turns (serve/engine.py ``_run_batch``)."""

from benchmark.layers import _stages


def read(ctx):
    return _stages.mean_ms(ctx, "serve/service_time")
