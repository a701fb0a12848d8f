"""Stage ``serve/forward`` a batch: ``predictor.predict`` until it returns —
the h2d of the batch and the enqueue, not the device's run (serve/engine.py
``_forward_legacy``)."""

from benchmark.layers import _stages


def read(ctx):
    return _stages.mean_ms(ctx, "serve/forward")
