"""Stage ``serve/mask/paste`` a batch: every record's 28x28 map pasted into
its request's raw frame and run-length coded, summed over the batch's
images (serve/engine.py ``_mask_stage``; ``eval.tester.mask_to_rle``)."""

from benchmark.layers import _stages


def read(ctx):
    return _stages.mean_ms(ctx, "serve/mask/paste")
