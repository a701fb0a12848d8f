"""Stage ``frontend/reply`` a request: the response document serialised to
JSON and written to the socket, on the request's own thread
(serve/frontend.py; a mask network's records carry their RLE counts)."""

from benchmark.layers import _stages


def read(ctx):
    return _stages.mean_ms(ctx, "frontend/reply")
