"""Stage ``serve/stage_row`` a request: the copy of the prepared image and
its ``im_info`` into the request's row of the staging batch, and the lock's
bookkeeping, on the request's own thread (serve/engine.py ``_write_row``)."""

from benchmark.layers import _stages


def read(ctx):
    return _stages.mean_ms(ctx, "serve/stage_row")
