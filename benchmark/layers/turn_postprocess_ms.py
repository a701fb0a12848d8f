"""Stage ``serve/postprocess`` a batch: the whole host loop over the batch's
images, box decode -> per-class NMS -> records (serve/engine.py
``_forward_legacy``)."""

from benchmark.layers import _stages


def read(ctx):
    return _stages.mean_ms(ctx, "serve/postprocess")
