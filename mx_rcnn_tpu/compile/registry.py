"""Unified compiled-program registry + persistent AOT warm-start.

Before this module, three drivers each kept their own shape-keyed
compile bookkeeping: the trainer's per-(program, bucket-shape)
``seen_programs`` set, the ``Predictor``'s four independent jit dicts
(``_predict``/``_predict_rpn``/``_packed_fns``/``_pyr_fn``), and the
serve engine's ``_seen_shapes``.  None of them talked to the persistent
XLA compilation cache that ``__graft_entry__``/the test suite already
rely on — every server boot recompiled every (bucket, batch) program
from scratch.

:class:`ProgramRegistry` unifies the three:

* **One key.**  :class:`ProgramKey` = ``(model-config digest, program
  kind, input shape, batch, dtype, sharding)``.  The config digest is a
  sha1 over ``config_to_dict(cfg)``, so two processes agree on program
  identity iff they agree on the *entire* frozen config tree.
* **One callable cache.**  ``register(kind, builder)`` +
  ``lookup(kind, static=...)`` replace the Predictor's ad-hoc dicts:
  builders are lazy, built-once, and LRU-evicted past ``max_programs``
  (multi-model serving needs a bound; XLA executables pin device memory).
* **One persistent cache.**  Where jax's compilation cache lives is
  decided in ONE place, :func:`setup_compile_cache`, which every entry
  point calls: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
  (jax reads the variable itself; no code path here redirects it), else
  one fixed git-ignored dir in the checkout (``.jax_cache``).  When the registry owns a
  cache base (the ``MXR_PROGRAM_CACHE`` env var or an explicit
  ``cache_base``) and the environment variable is unset, it points jax's
  compilation cache at a machine-fingerprint dir extended with the dtype
  and cache-schema version (``registry_cache_dir``) and drops
  ``jax_persistent_cache_min_compile_time_secs`` to 0 so even tiny-model
  programs persist.  A sidecar *marker manifest*
  (``<dir>/programs/<keyhash>.json``, one JSON file per program) records
  which programs a previous process already compiled: on the first
  in-process dispatch of a key, a present-and-matching marker counts as
  ``compile/aot_hit`` (XLA will load the executable from disk), a
  missing one as ``compile/aot_miss``, and a present-but-mismatching one
  as ``compile/key_collision`` (treated as a miss — the marker is
  overwritten, never trusted).
* **What XLA really did.**  The marker manifest is a *forecast* — it says
  what a previous process left behind, not what jax then does.  The truth
  comes from jax itself: ``jax.monitoring`` listeners, registered once a
  process, keep :func:`xla_counters` — every program the process builds
  (the eager one-op ones too), the seconds that took, every retrace, and
  the persistent cache's hits and misses.  ``snapshot()`` carries them
  under ``counters`` beside the forecast.
* **One compile-seconds histogram.**  ``record_compile_seconds`` feeds
  the PR-6 ``Hist`` primitive per program kind plus the aggregate
  ``compile/seconds`` telemetry hist, so the report can show the compile
  tail the AOT path is deleting.

Foreign-machine safety is inherited from ``machine_cache_dir``: AOT CPU
executables compiled on a host with different CPU features are rejected
at load (and documented to risk SIGILL if forced), so the fingerprint
keys them out of reach entirely — the registry only *extends* that key,
it never weakens it.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

from mx_rcnn_tpu import telemetry
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.telemetry import Hist

# bump when the marker-manifest layout or the ProgramKey fields change:
# a new schema gets a fresh fingerprint dir, so stale manifests from an
# older code version are ignored rather than misread
CACHE_SCHEMA = "mxr-programs-v1"

ENV_CACHE_BASE = "MXR_PROGRAM_CACHE"

# jax's own variable for the persistent compilation cache: where it is set,
# the XLA entries live there and nothing in this repo points jax elsewhere
ENV_JAX_CACHE = "JAX_COMPILATION_CACHE_DIR"

# the program's cache when the environment names none: ONE fixed path in
# the checkout (git-ignored).  The path is part of what makes an entry
# findable again, so it is never derived from a temp name, a pid, a time
# or the host's name.
DEFAULT_JAX_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

INFER_DTYPES = ("float32", "bfloat16", "int8", "int8-activation")

# schema tag for the activation-scale manifest persisted next to the AOT
# program markers — bump when the calibration doc layout changes
ACT_SCALES_SCHEMA = "mxr-act-scales-v1"


def config_digest(cfg) -> str:
    """sha1 over the full frozen config tree (16 hex chars).

    ``None`` (duck-typed predictors in tests) digests to ``"none"`` —
    such registries still dedupe in-process but share one manifest
    namespace."""
    if cfg is None:
        return "none"
    doc = dataclasses.asdict(cfg)
    blob = json.dumps(doc, sort_keys=True, default=str)
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


def plan_signature(plan) -> str:
    """Stable string identity of a MeshPlan (or ``"none"``): programs
    lowered against different meshes are different executables."""
    if plan is None:
        return "none"
    try:
        return (f"d{plan.n_data}m{plan.n_model}s{plan.n_space}"
                f"x{len(plan.mesh.devices.flat)}")
    except Exception:
        return "plan"


def registry_cache_dir(base: Optional[str] = None,
                       dtype: str = "float32") -> str:
    """Machine-fingerprint cache dir extended with dtype + cache schema.

    Builds on ``__graft_entry__.machine_cache_dir`` (arch, CPU feature
    flags, jax version) and folds in the inference dtype and
    :data:`CACHE_SCHEMA` — a bf16 replica and an f32 replica over the
    same base get disjoint dirs, and a jax upgrade or manifest-layout
    change silently starts cold instead of misusing stale entries."""
    from __graft_entry__ import machine_cache_dir

    base = base or os.environ.get(ENV_CACHE_BASE) \
        or os.environ.get("JAX_TEST_CACHE", "/tmp/jax_test_cache")
    return machine_cache_dir(base, extra=(f"dtype={dtype}", CACHE_SCHEMA))


def setup_compile_cache() -> str:
    """Place jax's persistent compilation cache for a program entry point
    (every driver calls this before its first compile) and return the
    directory.  Where the cache is already placed it is left alone:
    ``JAX_COMPILATION_CACHE_DIR`` reaches this config through jax itself,
    and a harness that runs a driver in-process (the test suite) has set
    its own.  Otherwise :data:`DEFAULT_JAX_CACHE`, so that a later process
    of the same checkout finds what this one compiled."""
    import jax

    placed = jax.config.jax_compilation_cache_dir
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_JAX_CACHE)
    return DEFAULT_JAX_CACHE


def configure_jax_cache(cache_dir: str) -> None:
    """Persist *every* compile (min_compile_time 0: the registry's warm
    boots depend on tiny programs hitting disk too, not just the >1 s
    flagship compiles jax's default filters for) and — unless
    ``JAX_COMPILATION_CACHE_DIR`` placed the cache from outside, which is
    never redirected — point jax's persistent compilation cache at
    ``cache_dir``.

    jax initializes its cache object at most once, on the first compile
    — and model/param init compiles typically run before any registry
    exists, pinning the cache to whatever dir was configured first.
    ``reset_cache()`` drops that instance so the next compile
    re-initializes against ``cache_dir``; without it the config update is
    silently ignored and nothing persists where the marker manifest says
    it does."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get(ENV_JAX_CACHE):
        return
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


# jax.monitoring's event names (jax 0.9: _src/dispatch.py, _src/compiler.py)
_EV_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_EV_JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_EV_COUNTED = {"/jax/compilation_cache/cache_hits": "persistent_cache_hits",
               "/jax/compilation_cache/cache_misses":
                   "persistent_cache_misses"}

_xla_lock = threading.Lock()
_xla_listening = False
_xla = {"xla_compiles": 0, "xla_compile_s": 0.0, "jaxpr_traces": 0,
        "persistent_cache_hits": 0, "persistent_cache_misses": 0}


def _on_xla_duration(event, duration, **_):
    if event == _EV_BACKEND_COMPILE:
        with _xla_lock:
            _xla["xla_compiles"] += 1
            _xla["xla_compile_s"] += duration
    elif event == _EV_JAXPR_TRACE:
        with _xla_lock:
            _xla["jaxpr_traces"] += 1


def _on_xla_event(event, **_):
    key = _EV_COUNTED.get(event)
    if key is not None:
        with _xla_lock:
            _xla[key] += 1


def listen_to_xla() -> None:
    """Register the ``jax.monitoring`` listeners behind
    :func:`xla_counters`, once a process (every registry asks)."""
    global _xla_listening
    with _xla_lock:
        if _xla_listening:
            return
        _xla_listening = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_xla_duration)
    monitoring.register_event_listener(_on_xla_event)


def xla_counters() -> dict:
    """What XLA did in this process since the first registry was built,
    monotone: ``xla_compiles`` / ``xla_compile_s`` — programs jax handed to
    the backend and the seconds until each was loaded (a program found in
    the persistent cache is counted too; its seconds are the load's);
    ``persistent_cache_hits`` / ``persistent_cache_misses`` — how many of
    them the cache served or did not; ``jaxpr_traces`` — every trace of a
    jitted function.  EVERY program counts, the eager one-op ones as well
    as the registry's own."""
    with _xla_lock:
        return dict(_xla)


@dataclasses.dataclass(frozen=True)
class ProgramKey:
    """Identity of one XLA program as the registry sees it."""

    digest: str          # config_digest(cfg)
    kind: str            # e.g. "predict", "train_step", "masks_packed"
    # full padded input shape (batch leading), optionally extended with
    # string tokens for non-shape statics baked into the executable (the
    # device-postprocess program appends e.g. "mpi=100"/"th=0.001" — two
    # runs differing only in those flags are different XLA programs)
    shape: Tuple[Any, ...]
    batch: int           # leading dim, kept explicit for the manifest
    dtype: str           # inference/compute dtype variant
    sharding: str        # plan_signature(plan)

    def fields(self) -> dict:
        return {"digest": self.digest, "kind": self.kind,
                "shape": list(self.shape), "batch": self.batch,
                "dtype": self.dtype, "sharding": self.sharding,
                "schema": CACHE_SCHEMA}

    def hash(self) -> str:
        blob = json.dumps(self.fields(), sort_keys=True)
        return hashlib.sha1(blob.encode()).hexdigest()[:20]


class ProgramRegistry:
    """Per-process registry of every program one model can dispatch.

    Parameters
    ----------
    cfg : frozen config (or None for duck-typed predictors)
    dtype : inference dtype variant this registry's programs run in
    plan : MeshPlan or None — folded into every key's sharding field
    cache_base : explicit persistent-cache base dir.  When given (or the
        ``MXR_PROGRAM_CACHE`` env var is set) the registry OWNS a cache
        dir: it keeps its marker manifest under ``registry_cache_dir``
        and — unless ``JAX_COMPILATION_CACHE_DIR`` already placed jax's
        cache from outside — points jax there too.  Otherwise it
        piggybacks marker files on whatever cache dir is already
        configured (``setup_compile_cache``/conftest), never touching
        global jax config — warm-start accounting still works.
    max_programs : LRU bound on *built callables* (not markers); None =
        unbounded.
    pinned : exempt this registry's callables from LRU eviction even
        when ``max_programs`` is set.  The multi-model ``ModelPool``
        pins a hot model's registry so its programs survive pressure
        from sibling models; mutable at runtime (``registry.pinned``).
    """

    def __init__(self, cfg=None, dtype: str = "float32", plan=None,
                 cache_base: Optional[str] = None,
                 max_programs: Optional[int] = None,
                 pinned: bool = False):
        if dtype not in INFER_DTYPES:
            raise ValueError(f"dtype must be one of {INFER_DTYPES}, "
                             f"got {dtype!r}")
        self.digest = config_digest(cfg)
        self.dtype = dtype
        self.sharding = plan_signature(plan)
        self.max_programs = max_programs
        self.pinned = bool(pinned)
        self._lock = threading.Lock()
        self._builders: Dict[str, Callable[..., Callable]] = {}
        self._fns: "OrderedDict[Tuple[str, Tuple], Callable]" = OrderedDict()
        self._seen: Dict[ProgramKey, dict] = {}
        # aot_hit / aot_miss are a FORECAST from the marker manifest (what a
        # previous process left, not what jax then does: a marker without
        # its cache entry still reads "hit").  What XLA really built or
        # loaded is xla_counters(), merged in by snapshot().
        self.counters: Dict[str, int] = {
            "programs": 0, "aot_hit": 0, "aot_miss": 0,
            "key_collisions": 0, "evictions": 0,
            # persistent cache could not be configured: serving goes on,
            # every boot compiles cold — counted so it cannot hide
            "cache_unavailable": 0,
        }
        self.compile_hist = Hist()
        listen_to_xla()

        base = cache_base or os.environ.get(ENV_CACHE_BASE)
        self.owns_cache = bool(base)
        if self.owns_cache:
            self.cache_dir: Optional[str] = registry_cache_dir(base, dtype)
            try:
                configure_jax_cache(self.cache_dir)
            except Exception as e:  # cache is an optimization, not a dep
                logger.warning("program registry: persistent cache "
                               "unavailable (%s)", e)
                self.counters["cache_unavailable"] += 1
                telemetry.get().counter("compile/cache_unavailable")
                self.cache_dir = None
        else:
            self.cache_dir = self._active_jax_cache_dir()

    @staticmethod
    def _active_jax_cache_dir() -> Optional[str]:
        try:
            import jax

            return jax.config.jax_compilation_cache_dir or None
        except Exception:
            return None

    # -- keys + marker manifest -----------------------------------------

    def key_for(self, kind: str, shape: Iterable) -> ProgramKey:
        # int-like tokens normalize to int (numpy scalars hash/serialize
        # differently); anything else (static-arg tags) stays a string
        shape = tuple(s if isinstance(s, str) else int(s) for s in shape)
        ints = [s for s in shape if not isinstance(s, str)]
        batch = int(ints[0]) if ints else 0
        return ProgramKey(self.digest, kind, shape, batch, self.dtype,
                          self.sharding)

    def _marker_path(self, key: ProgramKey) -> Optional[str]:
        if not self.cache_dir:
            return None
        return os.path.join(self.cache_dir, "programs", key.hash() + ".json")

    def _probe_marker(self, key: ProgramKey) -> str:
        """'hit' | 'miss' | 'collision' for this key's on-disk marker."""
        path = self._marker_path(key)
        if not path or not os.path.exists(path):
            return "miss"
        try:
            with open(path) as f:
                stored = json.load(f)
        except (OSError, ValueError):
            return "collision"  # unreadable marker: never trust it
        return "hit" if stored == key.fields() else "collision"

    def _write_marker(self, key: ProgramKey) -> None:
        path = self._marker_path(key)
        if not path:
            return
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = f"{path}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump(key.fields(), f, sort_keys=True)
            os.replace(tmp, path)  # atomic: concurrent ranks race benignly
        except OSError as e:
            logger.warning("program registry: marker write failed (%s)", e)

    # -- activation-scale manifest (int8-activation calibration) ---------

    def act_scales_path(self) -> Optional[str]:
        """Where this registry persists calibrated activation scales —
        next to the AOT program markers, keyed by config digest, so a
        warm boot of the same config finds the same calibration the AOT
        executables were built against."""
        if not self.cache_dir:
            return None
        return os.path.join(self.cache_dir, "programs",
                            f"act_scales-{self.digest}.json")

    def save_act_scales(self, tensors: Dict[str, dict]) -> Optional[str]:
        """Persist per-tensor calibration scales (``{"tensor": {"absmax",
        "scale"}}``) atomically; returns the path (None when no cache dir
        is configured — calibration then lives only in-process)."""
        path = self.act_scales_path()
        if not path:
            return None
        doc = {"schema": ACT_SCALES_SCHEMA, "digest": self.digest,
               "tensors": tensors}
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = f"{path}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f, sort_keys=True)
            os.replace(tmp, path)
        except OSError as e:
            logger.warning("program registry: act-scales write failed (%s)",
                           e)
            return None
        return path

    def load_act_scales(self) -> Optional[Dict[str, dict]]:
        """Load the persisted calibration manifest for this config digest
        (None when absent/unreadable/schema-mismatched — callers fall
        back to the weight-only int8 behavior)."""
        path = self.act_scales_path()
        if not path or not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return None
        if (doc.get("schema") != ACT_SCALES_SCHEMA
                or doc.get("digest") != self.digest):
            return None
        tensors = doc.get("tensors")
        return tensors if isinstance(tensors, dict) else None

    # -- dispatch accounting --------------------------------------------

    def note_dispatch(self, kind: str, shape: Iterable[int]) -> bool:
        """First-seen accounting for one dispatch.  Returns True exactly
        once per (kind, shape) per process — the caller's "this dispatch
        compiles" signal (steady state must return False forever after).

        On the first sighting, probes the marker manifest — a FORECAST,
        not what XLA then does (that is :func:`xla_counters`): a matching
        marker from a previous process is an ``aot_hit`` (the persistent
        cache should serve the executable), anything else an ``aot_miss``
        (plus ``key_collision`` when a marker exists but disagrees with
        the key — it is overwritten, not trusted)."""
        key = self.key_for(kind, shape)
        with self._lock:
            if key in self._seen:
                return False
            probe = self._probe_marker(key)
            self._seen[key] = {"aot": probe, "t": time.time()}
            self.counters["programs"] += 1
            if probe == "collision":
                self.counters["key_collisions"] += 1
            if probe == "hit":
                self.counters["aot_hit"] += 1
            else:
                self.counters["aot_miss"] += 1
        tel = telemetry.get()
        tel.counter("compile/aot_hit" if probe == "hit"
                    else "compile/aot_miss")
        if probe == "collision":
            tel.counter("compile/key_collision")
        tel.meta("compile/program", kind=kind, shape=list(key.shape),
                 dtype=self.dtype, sharding=self.sharding,
                 digest=self.digest, aot=probe)
        self._write_marker(key)
        return True

    def record_compile_seconds(self, kind: str, shape: Iterable[int],
                               seconds: float) -> None:
        """Observe one program's first-dispatch wall time (compile +
        first run) into the per-kind and aggregate compile histograms."""
        self.compile_hist.observe(seconds)
        tel = telemetry.get()
        tel.observe("compile/seconds", seconds)
        tel.observe(f"compile/seconds/{kind}", seconds)
        key = self.key_for(kind, shape)
        with self._lock:
            info = self._seen.get(key)
            if info is not None:
                info["compile_s"] = seconds

    # -- callable cache --------------------------------------------------

    def register(self, kind: str, builder: Callable[..., Callable]) -> None:
        """Declare how to build the jitted callable for ``kind``.  The
        builder receives the static args later passed to ``lookup`` and
        returns the callable; it runs at most once per distinct statics
        (until LRU-evicted)."""
        with self._lock:
            self._builders[kind] = builder

    def lookup(self, kind: str, static: Tuple = ()) -> Callable:
        """Build-or-fetch the callable for (kind, static), LRU-ordered."""
        ck = (kind, tuple(static))
        with self._lock:
            fn = self._fns.get(ck)
            if fn is not None:
                self._fns.move_to_end(ck)
                return fn
            builder = self._builders.get(kind)
        if builder is None:
            raise KeyError(f"no builder registered for program kind "
                           f"{kind!r} (have {sorted(self._builders)})")
        fn = builder(*ck[1])
        with self._lock:
            # lost-race check: another thread may have built it meanwhile
            if ck not in self._fns:
                self._fns[ck] = fn
                while (not self.pinned
                       and self.max_programs is not None
                       and len(self._fns) > self.max_programs):
                    evicted, _ = self._fns.popitem(last=False)
                    self.counters["evictions"] += 1
                    telemetry.get().counter("compile/eviction")
                    logger.info("program registry: evicted %r "
                                "(max_programs=%d)", evicted,
                                self.max_programs)
            self._fns.move_to_end(ck)
            return self._fns[ck]

    def programs(self) -> int:
        with self._lock:
            return len(self._seen)

    def snapshot(self) -> dict:
        """JSON-able state for ``/metrics`` and the warmup log."""
        with self._lock:
            counters = {**self.counters, **xla_counters()}
            seen = [dict(kind=k.kind, shape=list(k.shape),
                         dtype=k.dtype, aot=v["aot"],
                         compile_s=round(v.get("compile_s", 0.0), 3))
                    for k, v in self._seen.items()]
        return {"digest": self.digest, "dtype": self.dtype,
                "sharding": self.sharding, "cache_dir": self.cache_dir,
                "owns_cache": self.owns_cache, "pinned": self.pinned,
                "counters": counters,
                "programs": seen,
                "compile_seconds": self.compile_hist.to_dict()}
