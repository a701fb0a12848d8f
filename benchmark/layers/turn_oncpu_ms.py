"""The dispatcher thread's CPU milliseconds in a turn: the window's CPU
seconds of ``serve/service_time`` over its turns (serve/engine.py
``_book_turn``, the thread's ``getrusage`` at the two claims that bound the
turn).  ``turn_ms`` less this is what the loop spent not running: waiting
for the GIL, a core or the device."""

from benchmark.layers import _cpu


def read(ctx):
    s = _cpu.per_use(ctx, ["serve/service_time"], "cpu_s",
                     "serve/service_time")
    return None if s is None else 1e3 * s
