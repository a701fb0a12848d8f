"""Mean of the engine's own ``queue_wait_ms`` (enqueue -> batch flush), as
each good response of the window carried it."""


def read(ctx):
    return ctx["window"].get("queue_wait_ms")
