"""Stage ``serve/readback`` a batch: ``jax.device_get`` of the outputs — the
wait for the device, so the program's whole run, plus the d2h
(serve/engine.py ``_forward_legacy``)."""

from benchmark.layers import _stages


def read(ctx):
    return _stages.mean_ms(ctx, "serve/readback")
