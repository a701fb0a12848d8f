"""Start-up before warm-up: ``setup/model`` + ``setup/params`` +
``setup/predictor`` (serve.py ``_build_engine``), from ``/metrics``'
``setup``."""


def read(ctx):
    setup = ctx["metrics_after"].get("setup") or {}
    parts = [setup.get(k) for k in ("model_s", "params_s", "predictor_s")]
    if any(p is None for p in parts):
        return None
    return sum(parts)
