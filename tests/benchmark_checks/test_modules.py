"""A configuration names its own modules (weights, reference, comparison,
operation counts, control), so a second architecture arrives as files; and
every window runs in a process that loaded its programs from the cache: the
marker that says so, and the child that is asked for where it is absent."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

import benchmark
from benchmark import harness
from benchmark.drivers import serve as drv

from . import tiny

C4 = harness.load_cell("c4-serve-open")


def test_modules_of_r101_c4_returns_todays_five():
    mods = harness.modules_of(C4["config"])
    assert {k: m.__name__ for k, m in mods.items()} == {
        "weights": "benchmark.weights",
        "reference": "benchmark.reference.frcnn_c4",
        "compare": "benchmark.compare", "flops": "benchmark.flops",
        "control": "benchmark.control"}
    assert tuple(mods) == harness.MODULE_KEYS


@pytest.mark.parametrize("modules, names", [
    (None, "modules"),
    ({k: v for k, v in C4["config"]["modules"].items() if k != "compare"},
     "'compare'"),
    (dict(C4["config"]["modules"], reference="benchmark.reference.frcnn_c5"),
     "'reference'"),
    (dict(C4["config"]["modules"], flops="benchmark_flops_misspelt"),
     "'flops'"),
], ids=["no-modules", "missing-key", "misspelt-module", "misspelt-package"])
def test_a_missing_or_misspelt_module_is_an_error_naming_the_key(modules,
                                                                  names):
    config = {k: v for k, v in C4["config"].items() if k != "modules"}
    if modules is not None:
        config["modules"] = modules
    with pytest.raises(RuntimeError, match=names):
        harness.modules_of(config)


def test_the_harness_reaches_the_c4_modules_through_the_key_alone():
    """ISSUE 27's grep: the command, the harness, the serve driver and the
    sweep name none of the C4 detector's modules."""
    for rel in ("run.py", "harness.py", "sweep.py", "drivers/serve.py"):
        text = open(os.path.join(harness.HERE, rel)).read()
        for word in ("frcnn_c4", "weights.make", "compare.compare",
                     "benchmark.flops", "benchmark.control"):
            assert word not in text, (rel, word)


# ------------------------------------------------------------- the marker

class Child:
    """Stands in for ``subprocess.run``: records the call and writes the
    marker, as ``run.py --precompile`` does at its end."""

    def __init__(self, spec):
        self.spec, self.calls = spec, []

    def __call__(self, argv, **kw):
        self.calls.append((argv, kw))
        harness.write_programs_marker(self.spec)
        return subprocess.CompletedProcess(argv, 0)


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path / "cache"))
    return tmp_path / "cache"


def test_marker_absent_asks_for_the_child_and_present_does_not(cache_dir):
    child = Child(C4)
    assert harness.ensure_programs_cached(C4, 2 ** 31 + 5, spawn=child) is True
    (argv, kw), = child.calls
    assert argv[0] == sys.executable and argv[1].endswith("benchmark/run.py")
    assert argv[2:] == ["--workload", "c4-serve-open", "--seed",
                        str(2 ** 31 + 5), "--seconds", "0", "--precompile"]
    assert kw["cwd"] == harness.ROOT and kw["stdout"] is sys.stderr
    assert os.listdir(cache_dir) == [os.path.basename(
        harness.programs_marker(C4))]
    # present: every later run starts as it always did, and so does the
    # other cell of the same configuration
    assert harness.ensure_programs_cached(C4, 7, spawn=child) is False
    closed = harness.load_cell("c4-serve-closed")
    assert harness.ensure_programs_cached(closed, 7, spawn=child) is False
    assert len(child.calls) == 1


def test_the_marker_is_keyed_by_configuration_flags_and_versions(
        cache_dir, monkeypatch):
    base = harness.programs_marker(C4)
    other = json.loads(json.dumps(C4))
    other["config"]["serve_flags"][2] = "8"
    assert harness.programs_marker(other) != base
    monkeypatch.setattr(harness, "versions",
                        lambda: {"jax": "0", "jaxlib": "0", "libtpu": "1"})
    assert harness.programs_marker(C4) != base
    monkeypatch.undo()
    assert set(harness.versions()) == {"jax", "jaxlib", "libtpu"}
    assert harness.versions()["jax"] == __import__("jax").__version__


@pytest.mark.parametrize("rc", [3, 0], ids=["child-fails", "no-marker-left"])
def test_a_child_that_fails_ends_the_run_with_no_result(cache_dir, rc,
                                                        capsys):
    """A child that exits non-zero, or exits 0 and leaves no marker, is not
    believed: the run ends non-zero and prints no result."""
    def child(argv, **kw):
        return subprocess.CompletedProcess(argv, rc)

    with pytest.raises(SystemExit) as e:
        harness.ensure_programs_cached(C4, 1, spawn=child)
    assert e.value.code == 3
    assert capsys.readouterr().out == ""


def test_precompile_finds_no_chip_here_and_leaves_no_marker(cache_dir,
                                                            monkeypatch,
                                                            capsys):
    """``run.py --precompile`` on this CPU: the look for a chip refuses it
    before anything is built; it never asks for a child of its own."""
    from benchmark import run

    monkeypatch.setattr(harness, "setup_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "ensure_programs_cached",
                        lambda *a, **k: pytest.fail("a child of the child"))
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "c4-serve-open", "--seed", "1", "--seconds",
                  "0", "--precompile"])
    assert e.value.code == 3
    assert not os.path.exists(harness.programs_marker(C4))
    assert capsys.readouterr().out == ""


def test_the_precompile_child_warms_the_server_and_stops_it(monkeypatch):
    """The child's work at the tiny size: weights, the server built and
    warmed, the generator's warm requests, no window — and it returns."""
    seen = []
    real = drv.loadgen.warm
    monkeypatch.setattr(drv.loadgen, "warm",
                        lambda *a, **k: (seen.append(len(a[1])),
                                         real(*a, **k)))
    drv.precompile(tiny.tiny_spec("c4-serve-closed"), 2 ** 31 + 11)
    assert seen == [8]          # the tiny pool's bodies, warmed once


# ------------------------------------- the second snapshot of /metrics

def test_metrics_after_is_taken_when_the_generator_reports(monkeypatch):
    """PERF.md section 7 row 9: the second snapshot follows the result
    line, before the generator's exit is waited for."""
    log = []

    class Gen:
        def __init__(self, *a, **kw):
            self.stdin = open(os.devnull, "w")
            self.stdout = iter([
                json.dumps({"event": "window", "t0": 1.0, "seconds": 1.0}),
                json.dumps({"event": "closed", "t": 2.0}),
                json.dumps({"event": "result", "attempted": 1, "failed": 0})])

        def wait(self):
            log.append("wait")
            return 0

        def poll(self):
            return 0

    monkeypatch.setattr(drv.subprocess, "Popen", Gen)
    monkeypatch.setattr(drv, "_get_metrics",
                        lambda sock: log.append("metrics") or {"n": len(log)})
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda chips: 1)
    cond = drv.Conductor({"traffic": {}}, "sock", None, 1)
    cond._drive()
    assert log == ["metrics", "metrics", "wait"]
    assert cond.out["metrics_before"] == {"n": 1}
    assert cond.out["metrics_after"] == {"n": 2}


# --------------------------------- a configuration arrives as files alone

STUBS = {
    "__init__.py": "",
    "weights.py": '''
import numpy as np
def make(net, seed):
    return {"head/w": np.full((net["width"],), float(seed % 7), np.float32)}
''',
    "reference.py": '''
def detect(flat, request_doc, net):
    """Opaque to the driver: here the mask every record should carry."""
    return {"mask": "m" * int(flat["head/w"][0]), "shape": request_doc["shape"]}
''',
    "compare.py": '''
def compare(sample, dense, net):
    """Reads the whole response: a record's extra key and a top-level one."""
    wrong = sum(1 for s, d in zip(sample, dense)
                for r in s["response"]["detections"] if r["mask"] != d["mask"])
    n = sum(len(s["response"]["detections"]) for s in sample)
    return {"records": float(n), "mask_faults": float(wrong),
            "versions_seen": float(sum("model_version" in s["response"]
                                       for s in sample))}
def judge(numbers, limits):
    compared = {k: (numbers[k], lim) for k, lim in limits.items()}
    ok = all(v >= lim if k == "records" else v <= lim
             for k, (v, lim) in compared.items())
    return ok, compared
''',
    "flops.py": '''
def predict_flops_per_image(net):
    return {"total": 2 * net["width"]}
def nms_work(n, max_out):
    return {"ops": n * max_out, "bytes": n}
def roofline_seconds(ops, nbytes, peaks):
    return (ops / peaks["bf16_flops_per_s"], "compute")
''',
    "control.py": '''
def control_numbers(config, traffic, seed, **kw):
    return {"records": 1.0, "mask_faults": 1.0, "versions_seen": 1.0}
''',
}


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_a_configuration_arrives_as_files_alone(tmp_path, monkeypatch):
    """``second-det`` exists only in this temporary copy: a configuration
    file whose ``modules`` name five new modules, an entry under ``configs``
    and a cell.  No file that was in the copy is edited, and the driver's
    half after the window (reference -> compare -> judge -> last line) runs
    on them; a record's extra key reaches the new comparison."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(harness.ROOT, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(root)
    os.makedirs(root / "benchmark" / "second")
    for name, text in STUBS.items():
        (root / "benchmark" / "second" / name).write_text(text)
    config = {
        "source": "a second detector", "network": "second", "dataset": "coco",
        "cfg": [], "serve_flags": [], "batch_per_chip": 2, "reduced": [],
        "net": {"num_classes": 3, "width": 8},
        "modules": {k: "benchmark.second." + k for k in harness.MODULE_KEYS},
        "correct": {"records": 1, "mask_faults": 0, "versions_seen": 1}}
    (root / "benchmark" / "configs" / "second-det.json").write_text(
        json.dumps(config))
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    bench["configs"].append({
        "name": "second-det", "source": "a second detector",
        "file": "benchmark/configs/second-det.json", "reduced": [],
        "why": "another parameter tree, reference and comparison"})
    bench["workloads"].append({
        "name": "second-serve-open", "config": "second-det",
        "traffic": "open-poisson-coco", "chips": 1, "why": "the second "
        "architecture under the open mix"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("second-serve-open")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _digests(root)
    assert {k: after[k] for k in before} == before   # nothing there edited

    # the copy's benchmark/ is where its new package is found; what the
    # repo's own package already holds resolves as before
    monkeypatch.setattr(benchmark, "__path__",
                        list(benchmark.__path__) + [str(root / "benchmark")])
    for name in [n for n in sys.modules if n.startswith("benchmark.second")]:
        monkeypatch.delitem(sys.modules, name)
    spec = tiny.tiny_spec("second-serve-open", root=str(root))
    assert spec["traffic"]["kind"] == "serve"
    mods = harness.modules_of(spec["config"])
    assert all(m.__file__.startswith(str(root)) for m in mods.values())
    assert harness.programs_marker(spec) != harness.programs_marker(C4)

    seed = 2 ** 31 + 3            # 2**31 + 3 = 5 mod 7: a mask of "mmmmm"
    config = dict(spec["config"], correct=config["correct"])
    flat = mods["weights"].make(config["net"], seed)
    good = {"cls": 1, "score": 0.9, "bbox": [0.0, 0.0, 9.0, 9.0],
            "mask": "mmmmm"}
    device = {"platform": "cpu", "kind": "cpu", "count": 1,
              "memory_peak_bytes": 1}
    metrics = {"setup_s": {"value": 1.0, "unit": "s"}}
    line, compared = drv.after_window(
        mods, config, flat, tiny.canned_window([good], {"model_version": 2}),
        metrics, device)
    doc = json.loads(line)
    assert doc["correct"] is True and doc["attempted"] == 3
    assert list(doc)[-1] == "compared"
    assert doc["compared"] == {"records": {"value": 1.0, "limit": 1},
                               "mask_faults": {"value": 0.0, "limit": 0},
                               "versions_seen": {"value": 1.0, "limit": 1}}
    assert compared["mask_faults"] == (0.0, 0)
    # the new output altered where it is produced: not correct
    line, _ = drv.after_window(
        mods, config, flat,
        tiny.canned_window([dict(good, mask="mmmm")], {"model_version": 2}),
        metrics, device)
    assert json.loads(line)["correct"] is False
    # its readers' ctx["flops"] is its own module
    assert mods["flops"].predict_flops_per_image(config["net"])["total"] == 16
