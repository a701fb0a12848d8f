"""Minor page faults a request inside ``serve/host_prep`` (resize, normalise
and pad into the bucket, on the request's own thread): a fresh prepared
image that the allocator took from the OS faults in page by page (7.5 MB in
4-KiB pages is about 1,800); one from a reused arena does not."""

from benchmark.layers import _cpu


def read(ctx):
    return _cpu.per_use(ctx, ["serve/host_prep"], "minflt", "serve/host_prep")
