"""p95 of (fire - due) on the generator's own clock: a starved generator
must not read as a fast server."""


def read(ctx):
    return ctx["window"].get("loadgen_late_ms")
