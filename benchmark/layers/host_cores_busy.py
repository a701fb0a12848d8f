"""Cores the serving process kept busy between the two ``/metrics``
snapshots: its CPU seconds (every thread's: the dispatcher, the request
threads, the runtime's) over the server's own seconds.  Against
``/metrics["host"]["cores"]`` it tells a host out of cores from one whose
threads wait for the GIL.  The load generator is a child process and is not
in it."""

from benchmark.layers import _cpu


def read(ctx):
    d = _cpu.host(ctx)
    if d is None or d[1] <= 0:
        return None
    return d[0] / d[1]
