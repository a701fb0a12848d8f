"""Program-registry unit tests (host-side: no model, no XLA compile).

Covers the marker-manifest protocol the AOT warm start rests on —
first-dispatch accounting, cross-instance (simulating cross-process)
hit/miss, the dtype/digest/sharding key axes, forged-marker collision
handling — and the LRU bound on built callables.  The cross-PROCESS
half of the story (a real second server boot loading executables from
the persistent XLA cache) lives in tests/test_warmstart.py.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from mx_rcnn_tpu.compile import (ProgramKey, ProgramRegistry, config_digest,
                                 registry_cache_dir)
from mx_rcnn_tpu.compile.registry import CACHE_SCHEMA


@pytest.fixture
def jax_cache_guard():
    """ProgramRegistry(cache_base=...) OWNS the process-global jax
    compilation cache config — restore the suite's machine-dir cache
    afterwards so later tests keep their warm compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
        # configure_jax_cache reset the live cache instance; reset again
        # so the suite re-initializes against its machine dir
        compilation_cache.reset_cache()


def test_note_dispatch_first_seen_once_and_markers(tmp_path,
                                                   jax_cache_guard):
    reg = ProgramRegistry(dtype="float32", cache_base=str(tmp_path))
    assert reg.owns_cache and reg.cache_dir.startswith(str(tmp_path))

    # first sighting: True (the "this dispatch compiles" signal), no
    # marker on disk yet → aot_miss
    assert reg.note_dispatch("predict", (2, 96, 128, 3)) is True
    assert reg.note_dispatch("predict", (2, 96, 128, 3)) is False
    assert reg.note_dispatch("predict", (2, 128, 96, 3)) is True
    assert reg.counters == {"programs": 2, "aot_hit": 0, "aot_miss": 2,
                            "key_collisions": 0, "evictions": 0,
                            "cache_unavailable": 0}

    # each first dispatch left a marker manifest entry
    markers = os.listdir(os.path.join(reg.cache_dir, "programs"))
    assert len(markers) == 2 and all(m.endswith(".json") for m in markers)
    key = reg.key_for("predict", (2, 96, 128, 3))
    with open(reg._marker_path(key)) as f:
        assert json.load(f) == key.fields()

    # a second registry over the SAME base (the "second process"):
    # matching markers are AOT hits, a new shape is still a miss
    reg2 = ProgramRegistry(dtype="float32", cache_base=str(tmp_path))
    assert reg2.note_dispatch("predict", (2, 96, 128, 3)) is True
    assert reg2.note_dispatch("predict", (2, 128, 96, 3)) is True
    assert reg2.note_dispatch("predict", (4, 96, 128, 3)) is True
    assert reg2.counters["aot_hit"] == 2
    assert reg2.counters["aot_miss"] == 1
    assert reg2.counters["key_collisions"] == 0


def test_key_axes_separate_cache_namespaces(tmp_path, jax_cache_guard):
    # dtype is folded into the FINGERPRINT DIR, not just the key: a bf16
    # replica and an f32 replica over one base never share entries
    d_f32 = registry_cache_dir(str(tmp_path), "float32")
    d_bf16 = registry_cache_dir(str(tmp_path), "bfloat16")
    assert d_f32 != d_bf16

    reg = ProgramRegistry(dtype="float32", cache_base=str(tmp_path))
    reg.note_dispatch("predict", (2, 96, 128, 3))
    reg_b = ProgramRegistry(dtype="bfloat16", cache_base=str(tmp_path))
    assert reg_b.note_dispatch("predict", (2, 96, 128, 3)) is True
    assert reg_b.counters["aot_miss"] == 1  # disjoint dir: no hit

    # kind / shape / digest each change the key hash within one dir
    k = reg.key_for("predict", (2, 96, 128, 3))
    assert reg.key_for("predict_rpn", (2, 96, 128, 3)).hash() != k.hash()
    assert reg.key_for("predict", (4, 96, 128, 3)).hash() != k.hash()
    other = ProgramKey("deadbeefdeadbeef", k.kind, k.shape, k.batch,
                       k.dtype, k.sharding)
    assert other.hash() != k.hash()
    assert k.fields()["schema"] == CACHE_SCHEMA


def test_forged_marker_counts_collision_and_is_overwritten(tmp_path,
                                                           jax_cache_guard):
    reg = ProgramRegistry(dtype="float32", cache_base=str(tmp_path))
    key = reg.key_for("predict", (2, 96, 128, 3))
    path = reg._marker_path(key)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    forged = dict(key.fields(), digest="0000000000000000")
    with open(path, "w") as f:
        json.dump(forged, f)

    # same hash path, different fields: a collision — counted, treated
    # as a miss (never trusted), and overwritten with the true fields
    assert reg.note_dispatch("predict", (2, 96, 128, 3)) is True
    assert reg.counters["key_collisions"] == 1
    assert reg.counters["aot_miss"] == 1 and reg.counters["aot_hit"] == 0
    with open(path) as f:
        assert json.load(f) == key.fields()

    # unreadable marker is also a collision, not a crash
    key2 = reg.key_for("predict_rpn", (2, 96, 128, 3))
    path2 = reg._marker_path(key2)
    with open(path2, "w") as f:
        f.write("{not json")
    assert reg.note_dispatch("predict_rpn", (2, 96, 128, 3)) is True
    assert reg.counters["key_collisions"] == 2


def test_lookup_lru_eviction_and_rebuild():
    # no cache_base: piggyback mode, global jax config untouched
    reg = ProgramRegistry(max_programs=2)
    calls = []

    def builder(*static):
        calls.append(static)
        return lambda: static

    reg.register("fn", builder)
    a = reg.lookup("fn", ("a",))
    b = reg.lookup("fn", ("b",))
    assert reg.lookup("fn", ("a",)) is a  # cached, LRU-refreshed
    assert calls == [("a",), ("b",)]

    c = reg.lookup("fn", ("c",))  # evicts LRU entry ("b")
    assert reg.counters["evictions"] == 1
    assert reg.lookup("fn", ("a",)) is a and reg.lookup("fn", ("c",)) is c
    assert calls == [("a",), ("b",), ("c",)]

    assert reg.lookup("fn", ("b",)) is not b  # evicted: rebuilt
    assert calls == [("a",), ("b",), ("c",), ("b",)]
    assert reg.counters["evictions"] == 2

    with pytest.raises(KeyError):
        reg.lookup("nope")


def test_multimodel_lru_pressure_pinned_registry_never_evicts():
    """The model-pool contract on the registry: each model owns its own
    registry (so one model's pressure never evicts a sibling's
    programs), LRU eviction under pressure increments the counter and
    an evicted callable is rebuilt on next lookup, and a PINNED
    registry — the pool pins the hot model's — never evicts no matter
    how far past ``max_programs`` it grows."""
    hot = ProgramRegistry(max_programs=2, pinned=True)
    cold = ProgramRegistry(max_programs=2)
    built = {"hot": [], "cold": []}

    def make_builder(name):
        def builder(*static):
            built[name].append(static)
            return lambda: (name, static)
        return builder

    hot.register("fn", make_builder("hot"))
    cold.register("fn", make_builder("cold"))

    # pinned: four distinct programs live in a max_programs=2 registry
    hot_fns = [hot.lookup("fn", (s,)) for s in "abcd"]
    assert hot.counters["evictions"] == 0
    assert len(hot._fns) == 4
    for s, fn in zip("abcd", hot_fns):
        assert hot.lookup("fn", (s,)) is fn  # all still cached
    assert built["hot"] == [("a",), ("b",), ("c",), ("d",)]
    assert hot.snapshot()["pinned"] is True

    # the cold sibling under identical pressure evicts...
    cold_a = cold.lookup("fn", ("a",))
    for s in "bcd":
        cold.lookup("fn", (s,))
    assert cold.counters["evictions"] == 2
    assert len(cold._fns) == 2
    # ...and an evicted program is rebuilt, not lost
    assert cold.lookup("fn", ("a",)) is not cold_a
    assert built["cold"].count(("a",)) == 2
    # cross-model isolation: cold's churn never touched hot's cache
    assert hot.counters["evictions"] == 0 and len(hot._fns) == 4

    # pinning is mutable at runtime (pool re-pins on policy change):
    # unpinning re-enables the bound on the NEXT insert
    hot.pinned = False
    hot.lookup("fn", ("e",))
    assert hot.counters["evictions"] == 3  # trimmed 5 -> 2
    assert len(hot._fns) == 2


def test_snapshot_shape_and_digest_stability():
    from mx_rcnn_tpu.config import generate_config

    cfg = generate_config("resnet50", "PascalVOC")
    assert config_digest(cfg) == config_digest(cfg)
    assert config_digest(cfg) != config_digest(
        generate_config("resnet50", "PascalVOC", TEST__NMS=0.11))
    assert config_digest(None) == "none"

    reg = ProgramRegistry(cfg, dtype="bfloat16")
    reg.note_dispatch("predict", (2, 96, 128, 3))
    reg.record_compile_seconds("predict", (2, 96, 128, 3), 0.25)
    snap = reg.snapshot()
    assert snap["dtype"] == "bfloat16"
    assert snap["digest"] == config_digest(cfg)
    assert snap["counters"]["programs"] == 1
    (prog,) = snap["programs"]
    assert prog["kind"] == "predict" and prog["compile_s"] == 0.25
    assert snap["compile_seconds"]["count"] == 1


# -- cache placement (one rule for every entry point) ----------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("with_program_cache", [False, True])
def test_registry_never_redirects_a_cache_placed_from_outside(
        tmp_path, monkeypatch, jax_cache_guard, with_program_cache):
    # JAX_COMPILATION_CACHE_DIR set: jax's cache lives there; a registry —
    # with or without --program-cache — keeps only its marker manifest
    # under its own base
    from mx_rcnn_tpu.compile import setup_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    placed = jax.config.jax_compilation_cache_dir
    assert setup_compile_cache() == placed
    base = str(tmp_path / "base") if with_program_cache else None
    reg = ProgramRegistry(dtype="float32", cache_base=base)
    assert jax.config.jax_compilation_cache_dir == placed
    assert reg.counters["cache_unavailable"] == 0
    assert reg.note_dispatch("predict", (2, 96, 128, 3)) is True
    manifest_home = base if with_program_cache else placed
    assert reg._marker_path(reg.key_for("predict", (2, 96, 128, 3))) \
        .startswith(manifest_home)


def test_unconfigurable_cache_is_counted_not_hidden(tmp_path, monkeypatch,
                                                    jax_cache_guard):
    # a base that cannot be a directory: serving goes on, cold, and says so
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    reg = ProgramRegistry(dtype="float32", cache_base=str(blocker))
    assert reg.cache_dir is None
    assert reg.counters["cache_unavailable"] == 1
    assert reg.snapshot()["counters"]["cache_unavailable"] == 1
    assert reg.note_dispatch("predict", (2, 96, 128, 3)) is True


_PLACEMENT_PROBE = """
import sys, jax
from mx_rcnn_tpu.compile import ProgramRegistry, setup_compile_cache
setup_compile_cache()
ProgramRegistry(dtype='float32')
if sys.argv[1]:
    ProgramRegistry(dtype='float32', cache_base=sys.argv[1])
print(jax.config.jax_compilation_cache_dir)
"""


def _placed_dir(env_dir, program_cache=""):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "MXR_PROGRAM_CACHE")}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run(
        [sys.executable, "-c", _PLACEMENT_PROBE, program_cache], cwd=REPO,
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


@pytest.mark.parametrize("with_program_cache", [False, True])
def test_fresh_process_cache_goes_where_the_variable_says(
        tmp_path, with_program_cache):
    env_dir = str(tmp_path / "xla")
    base = str(tmp_path / "base") if with_program_cache else ""
    assert _placed_dir(env_dir, base) == env_dir


def test_fresh_process_default_cache_is_one_fixed_path_in_the_checkout():
    from mx_rcnn_tpu.compile.registry import DEFAULT_JAX_CACHE

    assert DEFAULT_JAX_CACHE == os.path.join(REPO, ".jax_cache")
    # identical across two fresh processes: no temp name, pid, time or host
    assert _placed_dir("") == _placed_dir("") == DEFAULT_JAX_CACHE
