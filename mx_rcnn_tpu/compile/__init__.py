"""Unified compiled-program registry + persistent AOT warm-start.

One place that knows every XLA program a driver can dispatch — the
trainer's per-(program, bucket-shape) step cache, the ``Predictor``'s
shape-keyed jit dicts, and the serve engine's predict path all route
their bookkeeping (and their jitted callables) through
:class:`~mx_rcnn_tpu.compile.registry.ProgramRegistry`, which in turn
keys the on-disk persistent compilation cache so a second process over
the same cache dir warms from disk instead of XLA (``compile/aot_hit``
vs ``compile/aot_miss`` in the telemetry stream).
"""

from mx_rcnn_tpu.compile.registry import (ProgramKey, ProgramRegistry,
                                          config_digest, configure_jax_cache,
                                          registry_cache_dir,
                                          setup_compile_cache)

__all__ = ["ProgramRegistry", "ProgramKey", "config_digest",
           "configure_jax_cache", "registry_cache_dir",
           "setup_compile_cache"]
