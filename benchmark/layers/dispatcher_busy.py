"""Share of its time the dispatcher thread spent inside turns, between the
two ``/metrics`` snapshots: seconds of ``serve/service_time`` over those
seconds plus the seconds of ``serve/idle`` (the wait when nothing is due) —
the thread is in one or the other but for the claim under the lock.  Both
are the program's own clocks, so the instant of a snapshot does not enter:
the second one comes well after the window (the generator's exit, the
tracer's join), and the idle period then under way is not booked before it
ends.  The one that is under way at the first snapshot (from the warm
requests' end to the window's first flush, about 0.3 s) is counted whole,
so a dispatcher that never waits reads about 99 %."""

from benchmark.layers import _stages


def read(ctx):
    turns = _stages.delta(ctx, "serve/service_time")
    idle = _stages.delta(ctx, "serve/idle")
    if turns is None or idle is None or turns[1] + idle[1] <= 0:
        return None
    return 100.0 * turns[1] / (turns[1] + idle[1])
