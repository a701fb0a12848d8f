"""1 - (union of the intervals in which an op ran on the device) / traced
window.  At a fixed offered rate this is mostly set by the rate; the
breakdown's idle gaps say what the host was doing."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t["window_s"] > 0 \
        else None
