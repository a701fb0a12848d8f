"""Start-up's warm-up: the whole of ``serve/warmup.py::warmup`` (stage
``setup/warmup``: one full batch an orientation through the real engine),
from ``/metrics``' ``setup``."""


def read(ctx):
    return (ctx["metrics_after"].get("setup") or {}).get("warmup_s")
