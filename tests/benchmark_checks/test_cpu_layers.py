"""The readers of the host CPU metrics (``benchmark/layers/``, PR 37): a
hand-made pair of ``/metrics`` documents gives the value worked out by hand,
a window in which the stage never ran gives None, and the documents of a
program without the CPU clocks (the parent of PR 37: stages of
``{"count", "sum_s"}`` alone, no ``serve/stage_row``, no ``frontend/reply``,
no ``"host"``) give None."""

import copy

import pytest

from benchmark import harness

BENCH = harness.load_json(harness.ROOT + "/BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


def stages(**rows):
    return {name.replace("__", "/"): {"count": c, "sum_s": s, "cpu_s": u,
                                       "minflt": f}
            for name, (c, s, u, f) in rows.items()}


# 10 turns and 150 requests in 10 s of the server's; 25 CPU seconds
BEFORE = {
    "t_s": 100.0,
    "host": {"cpu_s": 50.0, "cores": 8},
    "stages": stages(
        serve__service_time=(4, 3.0, 1.0, 40),
        serve__postprocess=(4, 2.0, 0.5, 0),
        frontend__read=(40, 0.75, 0.25, 0),
        frontend__decode=(40, 0.25, 0.2, 0),
        serve__host_prep=(40, 0.5, 0.3, 72_000),
        serve__stage_row=(40, 0.1, 0.05, 0),
        frontend__reply=(40, 0.2, 0.1, 0)),
}
AFTER = {
    "t_s": 110.0,
    "host": {"cpu_s": 75.0, "cores": 8},
    "stages": stages(
        serve__service_time=(14, 10.0, 4.0, 140),
        serve__postprocess=(14, 7.0, 2.5, 0),
        frontend__read=(190, 3.0, 1.0, 0),
        frontend__decode=(190, 1.0, 0.8, 0),
        serve__host_prep=(190, 2.0, 1.2, 342_000),
        serve__stage_row=(190, 0.4, 0.2, 0),
        frontend__reply=(190, 0.8, 0.4, 0)),
}

# metric -> (the value by hand, the stage whose count stands still in a
# window in which it never ran; None: a rate of the process)
CASES = {
    "turn_oncpu_ms": (300.0, "serve/service_time"),
    "turn_post_oncpu_ms": (200.0, "serve/postprocess"),
    # (0.75 + 0.6 + 0.9 + 0.15 + 0.3) s over 150 requests
    "request_oncpu_ms": (18.0, "serve/host_prep"),
    "host_prep_minflt": (1800.0, "serve/host_prep"),
    "stage_row_ms": (2.0, "serve/stage_row"),
    "frontend_reply_ms": (4.0, "frontend/reply"),
    "host_cores_busy": (2.5, None),
}


def read(name, before, after):
    one = dict(BENCH, per_layer=[m for m in BENCH["per_layer"]
                                 if m["name"] == name])
    assert len(one["per_layer"]) == 1, name
    ctx = {"metrics_before": before, "metrics_after": after}
    return harness.read_layers(one, CELLS[0], ctx).get(name)


def parents(doc):
    """The same document as the parent of PR 37 serves it."""
    doc = copy.deepcopy(doc)
    del doc["host"]
    for name in ("serve/stage_row", "frontend/reply"):
        del doc["stages"][name]
    doc["stages"] = {k: {"count": v["count"], "sum_s": v["sum_s"]}
                     for k, v in doc["stages"].items()}
    return doc


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_entry_lists_every_cell_and_moves_the_rate(name):
    m = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert m["workloads"] == CELLS and len(CELLS) == 5
    assert m["moves"] == "serve_imgs_per_s"
    assert m["source"] == ("program_counter" if name in (
        "host_prep_minflt", "host_cores_busy") else "program_span")
    assert m["better"] == "lower"


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_hand_made_window_gives_the_value_worked_out_by_hand(name):
    got = read(name, BEFORE, AFTER)
    unit = next(m["unit"] for m in BENCH["per_layer"] if m["name"] == name)
    assert got == {"value": pytest.approx(CASES[name][0]), "unit": unit}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_parents_documents_give_none(name):
    assert read(name, parents(BEFORE), parents(AFTER)) is None
    # and those of a program from before the stage clocks
    old = {"counters": {"batches": 3, "served": 9, "recompiles": 2}}
    assert read(name, old, dict(old, options={"batch_size": 16})) is None


@pytest.mark.parametrize("name", sorted(n for n, c in CASES.items() if c[1]))
def test_a_window_in_which_the_stage_never_ran_gives_none(name):
    after = copy.deepcopy(AFTER)
    stage = CASES[name][1]
    after["stages"][stage] = dict(BEFORE["stages"][stage])
    assert read(name, BEFORE, after) is None


def test_host_cores_busy_needs_the_servers_clock_to_have_moved():
    assert read("host_cores_busy", BEFORE, dict(AFTER, t_s=100.0)) is None
    # the cores are the ceiling, not part of the reading
    after = copy.deepcopy(AFTER)
    after["host"]["cores"] = 2
    assert read("host_cores_busy", BEFORE, after)["value"] == 2.5


def test_request_oncpu_ms_needs_all_five_clocks():
    for stage in ("frontend/read", "serve/stage_row", "frontend/reply"):
        before, after = copy.deepcopy(BEFORE), copy.deepcopy(AFTER)
        del before["stages"][stage]
        del after["stages"][stage]
        assert read("request_oncpu_ms", before, after) is None, stage
