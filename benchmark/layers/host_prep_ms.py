"""Stage ``serve/host_prep`` a request: resize, normalise and pad into the
bucket, on the request's own thread (serve/engine.py ``submit``)."""

from benchmark.layers import _stages


def read(ctx):
    return _stages.mean_ms(ctx, "serve/host_prep")
