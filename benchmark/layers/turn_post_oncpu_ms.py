"""The dispatcher thread's CPU milliseconds in a turn's host post-process:
the window's CPU seconds of ``serve/postprocess`` (decode, per-class NMS and
the records of every image of the batch) over its batches.  Against
``turn_postprocess_ms`` the difference is the loop's wait: it neither does
I/O nor waits for the device, so it waits for the GIL or a core."""

from benchmark.layers import _cpu


def read(ctx):
    s = _cpu.per_use(ctx, ["serve/postprocess"], "cpu_s",
                     "serve/postprocess")
    return None if s is None else 1e3 * s
