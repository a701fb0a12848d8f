"""What every driver shares: finding a cell's files by name, the device as
jax reports it, the table of peaks, the per-layer readers and the result's
last line.  Nothing here knows a configuration, a traffic mix or a metric by
name: those are files that ``BENCHMARK.json`` points at."""

from __future__ import annotations

import hashlib
import importlib
import importlib.metadata
import importlib.util
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")     # fixed: the path is in the key
TMP_DIR = os.path.join(ROOT, ".bench_tmp")       # traces, removed after reading


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> dict:
    """The cell's entry, its configuration and its traffic, found by name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(root, "benchmark", "traffic",
                                     cell["traffic"] + ".json"))
    return {"bench": bench, "cell": cell, "config": config,
            "traffic": traffic}


MODULE_KEYS = ("weights", "reference", "compare", "flops", "control")


def modules_of(config: dict) -> dict:
    """The configuration's own modules, imported by the names its file
    gives under ``modules`` (README, "A configuration"): {key: module} for
    every key of ``MODULE_KEYS``.  A key that is missing, or a name that
    does not import, is an error that names the key."""
    names = config.get("modules")
    if not isinstance(names, dict):
        raise RuntimeError("the configuration has no 'modules' object; it "
                           f"has to name {', '.join(MODULE_KEYS)}")
    out = {}
    for key in MODULE_KEYS:
        if key not in names:
            raise RuntimeError(f"the configuration's 'modules' lacks "
                               f"{key!r}")
        try:
            out[key] = importlib.import_module(names[key])
        except ImportError as e:
            raise RuntimeError(f"the configuration's modules[{key!r}] = "
                               f"{names[key]!r} does not import: {e}") from e
    return out


def metrics_of(bench: dict, group: str, workload: str) -> list:
    """The metrics of ``end_to_end`` / ``per_layer`` this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def setup_compile_cache() -> str:
    """jax's persistent cache at a fixed path inside the checkout, every
    program kept, before the program's own entry point looks (it leaves a
    cache that is already placed alone)."""
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no eviction, whatever the environment says: with a size limit jax
    # scans the directory on every write and one entry without its
    # timestamp file (seen on the chip machine) stops every later write
    jax.config.update("jax_compilation_cache_max_size", -1)
    return CACHE_DIR


def versions() -> dict:
    """What compiles the programs, read without importing any of it."""
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def programs_marker(spec: dict) -> str:
    """The file in ``CACHE_DIR`` that says this checkout's cache holds the
    cell's programs: keyed by the configuration as it is run (flags and
    sizes), the driver and the jax / jaxlib / libtpu versions.  The checkout
    path is in jax's own key, and the marker lies inside the cache it
    speaks of, so the two are emptied together."""
    key = json.dumps({"config": spec["config"],
                      "kind": spec["traffic"]["kind"],
                      "versions": versions()}, sort_keys=True)
    return os.path.join(CACHE_DIR, "programs-"
                        + hashlib.sha256(key.encode()).hexdigest()[:20]
                        + ".json")


def write_programs_marker(spec: dict) -> None:
    path = programs_marker(spec)
    os.makedirs(CACHE_DIR, exist_ok=True)
    with open(path + ".tmp", "w") as f:
        json.dump({"config": spec["cell"]["config"],
                   "kind": spec["traffic"]["kind"],
                   "versions": versions()}, f)
    os.replace(path + ".tmp", path)


def ensure_programs_cached(spec: dict, seed: int, spawn=subprocess.run,
                           limit_s: float = 1000.0) -> bool:
    """Before this process touches jax (the chip belongs to one process at a
    time): where the marker is absent, build and warm the cell's programs
    once in a child that exits — ``run.py --precompile``: the same server,
    the same flags, no window — so that every measured window runs in a
    process that loaded its programs from the cache.  -> whether a child
    ran.  A child that fails ends this run with no result."""
    marker = programs_marker(spec)
    if os.path.exists(marker):
        return False
    argv = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
            spec["cell"]["name"], "--seed", str(seed), "--seconds", "0",
            "--precompile"]
    print(f"benchmark: no marker {os.path.basename(marker)} in {CACHE_DIR}; "
          f"compiling in a child first", file=sys.stderr, flush=True)
    try:
        rc = spawn(argv, stdout=sys.stderr, cwd=ROOT,
                   timeout=limit_s).returncode
    except subprocess.TimeoutExpired:   # run() has killed and reaped it
        rc = 124
    if rc != 0 or not os.path.exists(marker):
        print(f"benchmark: the pre-compile child exited {rc}; no result",
              file=sys.stderr)
        raise SystemExit(rc or 3)
    return True


def device_doc() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> dict:
    """The device, or exit non-zero with no result where it is no TPU or
    holds fewer chips than the cell asks for."""
    doc = device_doc()
    print(f"device: {json.dumps(doc)}  cpu_count: {os.cpu_count()}",
          file=sys.stderr)
    if doc["platform"] != "tpu" or doc["count"] < chips:
        print(f"benchmark: needs {chips} TPU chip(s), found {doc}; "
              f"nothing built, no result", file=sys.stderr)
        raise SystemExit(3)
    return doc


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table or kind.startswith("_"):
        raise RuntimeError(f"device_kind {kind!r} is not in peaks.json")
    return table[kind]


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes of HBM taken on the fullest of the chips used, as jax
    reports it: the peak of live buffers (``peak_bytes_in_use``) plus the
    peak the runtime reserved for the loaded programs' scratch
    (``peak_bytes_reserved``) — on the TPU runtime a program's temporaries
    are reserved outside ``bytes_in_use`` (PERF.md section 4)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    print(f"memory_stats[0]: {json.dumps(stats[0])}", file=sys.stderr)
    return max(int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0)) for s in stats)


def read_layers(bench: dict, workload: str, ctx: dict) -> dict:
    """Each per-layer metric of this cell through its own reader
    (``layers/<metric>.py``, ``read(ctx) -> number or None``).  A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics_of(bench, "per_layer", workload):
        path = os.path.join(HERE, "layers", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "benchmark.layers." + m["name"].replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, compared: dict, breakdown=None) -> str:
    """The contract's last line.  ``compared`` (each number beside its
    limit) comes last; it also goes to standard error as the last lines."""
    doc = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        doc["breakdown"] = breakdown
    doc["compared"] = compared
    return json.dumps(doc)
