"""The p50 over **all** requests due in the window of (response end - due
instant), a failed, shed, malformed or missing answer reading window +
drain.  What a serving user feels; it stands among the per-layer metrics
because its run-to-run spread (PERF.md section 6) is wider than any bound
the contract admits."""


def read(ctx):
    return ctx["window"].get("serve_p50_ms")
