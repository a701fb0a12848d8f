"""BENCHMARK.json is well-formed, every name resolves to a file, and a cell
arrives as data alone."""

import json
import os
import re
import shutil

import pytest

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
ALL_METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
# benchmark/README.md, "A configuration": what each module must provide
MODULE_FUNCTIONS = {
    "weights": ("make",), "reference": ("detect",),
    "compare": ("compare", "judge"),
    "flops": ("predict_flops_per_image", "nms_work", "roofline_seconds"),
    "control": ("control_numbers",)}


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check with all 24 cells must fit: 2 + 14 x 24 runs
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert all(os.path.isdir(os.path.join(harness.ROOT, p))
               for p in BENCH["paths"])
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        < 64 * 1024


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"]
                         + ALL_METRICS, ids=lambda e: e["name"])
def test_names_and_units(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    for key in ("why", "layer"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], ALL_METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_resolve(cell):
    spec = harness.load_cell(cell["name"])
    assert os.path.exists(os.path.join(
        harness.HERE, "drivers", spec["traffic"]["kind"] + ".py"))
    # the configuration's own modules import and offer what the README's
    # table says the harness calls
    mods = harness.modules_of(spec["config"])
    assert set(mods) == set(MODULE_FUNCTIONS)
    for key, functions in MODULE_FUNCTIONS.items():
        for fn in functions:
            assert callable(getattr(mods[key], fn, None)), (key, fn)
    assert cell["chips"] in (1, 4)
    assert len(spec["config"]["source"]) <= 200
    assert spec["config"]["reduced"] == next(
        c for c in BENCH["configs"] if c["name"] == cell["config"])["reduced"]
    assert spec["config"]["correct"], "a cell is judged by stated limits"
    # every cell reports setup_s, another end-to-end metric and a layer one
    e2e = [m["name"] for m in harness.metrics_of(BENCH, "end_to_end",
                                                 cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_of(BENCH, "per_layer", cell["name"])


def test_every_config_is_used_and_four_chip_quota():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_bounds(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}
    assert 0.01 <= metric["bound"] <= 0.1
    assert metric["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_has_reader_and_moves(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert os.path.exists(os.path.join(harness.HERE, "layers",
                                       metric["name"] + ".py"))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert metric["moves"] in e2e
    cells = metric.get("workloads", [w["name"] for w in BENCH["workloads"]])
    for cell in cells:
        reported = [m["name"] for m in harness.metrics_of(
            BENCH, "end_to_end", cell)]
        assert metric["moves"] in reported
    if metric["name"].endswith("_roofline") or "_roofline." in metric["name"] \
            or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_readers_return_none_when_there_is_nothing_to_read():
    empty = {"trace": {"modules": {}, "op_time": {}, "busy_s": 0.0,
                       "window_s": 0.0},
             "window": {}, "config": harness.load_cell(
                 BENCH["workloads"][0]["name"])["config"],
             "metrics_before": {"counters": {"batches": 3, "served": 9,
                                             "recompiles": 2}},
             "metrics_after": {"counters": {"batches": 3, "served": 9,
                                            "recompiles": 2},
                               "options": {"batch_size": 16}},
             "peaks": harness.peaks_for("TPU v5 lite"),
             "flops": harness.modules_of(harness.load_cell(
                 BENCH["workloads"][0]["name"])["config"])["flops"]}
    out = harness.read_layers(BENCH, BENCH["workloads"][0]["name"], empty)
    # nothing traced, nothing served: only the count of recompiles (0) reads
    assert set(out) == {"recompiles_in_window"}
    for name, doc in out.items():
        assert "roofline" not in name and "mfu" not in name


def test_unknown_device_kind_is_an_error():
    with pytest.raises(RuntimeError):
        harness.peaks_for("TPU v9 imaginary")
    with pytest.raises(RuntimeError):
        harness.peaks_for("_source")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_a_cell_arrives_as_data_alone(tmp_path):
    """``c4-serve-burst`` exists only in this temporary copy: one traffic
    file and one ``workloads`` entry, no code — and the harness resolves it
    to the driver that already runs the ``burst`` arrival.  (The closed-loop
    cell of ``BENCHMARK.json`` arrived the same way.)"""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(harness.ROOT, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({
        "name": "c4-serve-burst", "config": "r101-c4",
        "traffic": "burst-16-coco", "chips": 1,
        "why": "bursts of 16 at the open cell's mean rate: batcher, shed "
               "valve"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "c4-serve-open" in m["workloads"]:
            m["workloads"].append("c4-serve-burst")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    open_mix = harness.load_json(os.path.join(
        harness.HERE, "traffic", "open-poisson-coco.json"))
    (root / "benchmark" / "traffic" / "burst-16-coco.json").write_text(
        json.dumps(dict(open_mix, arrival="burst", burst=16)))
    spec = harness.load_cell("c4-serve-burst", root=str(root))
    assert spec["traffic"]["arrival"] == "burst"
    assert spec["traffic"]["kind"] == "serve"
    assert spec["config"]["network"] == "resnet101"
    names = [m["name"] for m in harness.metrics_of(
        spec["bench"], "per_layer", "c4-serve-burst")]
    assert "batch_fill" in names and "predict_mfu" in names
    e2e = [m["name"] for m in harness.metrics_of(
        spec["bench"], "end_to_end", "c4-serve-burst")]
    assert e2e == ["setup_s", "serve_imgs_per_s"]
    assert "serve_p95_ms" in names


def test_the_closed_cell_is_the_open_cells_bodies_in_a_closed_loop():
    spec = harness.load_cell("c4-serve-closed")
    assert spec["traffic"]["arrival"] == "closed"
    assert spec["traffic"]["clients"] == 2 * spec["config"]["batch_per_chip"]
    assert spec["traffic"]["bodies"] == harness.load_cell(
        "c4-serve-open")["traffic"]["bodies"]
