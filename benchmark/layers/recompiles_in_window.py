"""First dispatches of a new shape between the two ``/metrics`` snapshots:
nothing may compile inside the window, so this should read 0."""


def read(ctx):
    return (ctx["metrics_after"]["counters"]["recompiles"]
            - ctx["metrics_before"]["counters"]["recompiles"])
