"""A request thread's CPU milliseconds a request: the window's CPU seconds
of its five clocks (``frontend/read``, ``frontend/decode``,
``serve/host_prep``, ``serve/stage_row``, ``frontend/reply``) over the
window's requests (the count of ``serve/host_prep``).  Times the requests a
second, the cores the request threads keep busy."""

from benchmark.layers import _cpu

STAGES = ["frontend/read", "frontend/decode", "serve/host_prep",
          "serve/stage_row", "frontend/reply"]


def read(ctx):
    s = _cpu.per_use(ctx, STAGES, "cpu_s", "serve/host_prep")
    return None if s is None else 1e3 * s
