"""Stage ``serve/assemble`` a batch: the two ``np.stack``s and the padding
rows that make the batch the device program takes (serve/engine.py
``_run_batch``)."""

from benchmark.layers import _stages


def read(ctx):
    return _stages.mean_ms(ctx, "serve/assemble")
