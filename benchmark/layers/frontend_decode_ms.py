"""Front end, a request: ``frontend/read`` (body off the socket +
``json.loads``) plus ``frontend/decode`` (base64 -> array), each on its
request's own thread (serve/frontend.py)."""

from benchmark.layers import _stages


def read(ctx):
    return _stages.mean_ms(ctx, "frontend/read", "frontend/decode")
