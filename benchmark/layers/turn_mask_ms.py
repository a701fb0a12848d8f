"""Stage ``serve/mask`` a batch: the whole second stage of a mask network's
turn — the records' boxes and classes back to the device, the mask
program's dispatch over the held pyramid, the read-back of the
probabilities, paste + RLE of every record (serve/engine.py
``_mask_stage``).  A network without a mask head has no such clock: None."""

from benchmark.layers import _stages


def read(ctx):
    return _stages.mean_ms(ctx, "serve/mask")
