"""Stage ``serve/post/nms`` a batch: ``per_class_nms``, summed over the
batch's images (serve/engine.py ``_forward_legacy``)."""

from benchmark.layers import _stages


def read(ctx):
    return _stages.mean_ms(ctx, "serve/post/nms")
