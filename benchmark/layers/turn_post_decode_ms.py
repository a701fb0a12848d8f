"""Stage ``serve/post/decode`` a batch: ``decode_image_boxes``, summed over
the batch's images (serve/engine.py ``_forward_legacy``)."""

from benchmark.layers import _stages


def read(ctx):
    return _stages.mean_ms(ctx, "serve/post/decode")
