"""The readers of the stage metrics (``benchmark/layers/``): a hand-made
pair of ``/metrics`` documents gives the value worked out by hand, a window
in which the stage never ran gives None, and a program that has no such
clock (the documents of before ``telemetry.stage``) gives None."""

import copy

import pytest

from benchmark import harness

BENCH = harness.load_json(harness.ROOT + "/BENCHMARK.json")
CELL = "c4-serve-closed"


def stages(**rows):
    return {name.replace("__", "/"): {"count": c, "sum_s": s}
            for name, (c, s) in rows.items()}


# 10 turns and 150 requests in 8 s of the dispatcher's, 1 s of them waiting
BEFORE = {
    "t_s": 100.0,
    "counters": {"served": 40, "batches": 4, "recompiles": 2,
                 "post_candidates": 600_000, "post_kept": 4_000},
    "stages": stages(
        serve__service_time=(4, 3.0), serve__idle=(2, 0.5),
        serve__assemble=(4, 0.2),
        serve__forward=(4, 0.25), serve__readback=(4, 0.5),
        serve__postprocess=(4, 2.0), serve__post__decode=(4, 1.25),
        serve__post__nms=(4, 0.5), serve__host_prep=(40, 0.5),
        frontend__read=(40, 0.75), frontend__decode=(40, 0.25)),
    "setup": {"model_s": 1.5, "params_s": 20.0, "predictor_s": 0.5,
              "warmup_s": 12.5},
    "compile": {"counters": {"xla_compiles": 300, "xla_compile_s": 9.5}},
}
AFTER = {
    "t_s": 108.0,
    "counters": {"served": 190, "batches": 14, "recompiles": 2,
                 "post_candidates": 3_000_000, "post_kept": 19_000},
    "options": {"batch_size": 16},
    "stages": stages(
        serve__service_time=(14, 10.0), serve__idle=(5, 1.5),
        serve__assemble=(14, 0.7),
        serve__forward=(14, 0.5), serve__readback=(14, 1.75),
        serve__postprocess=(14, 7.0), serve__post__decode=(14, 4.25),
        serve__post__nms=(14, 2.0), serve__host_prep=(190, 2.0),
        frontend__read=(190, 3.0), frontend__decode=(190, 1.0)),
    "setup": dict(BEFORE["setup"]),
    "compile": {"counters": {"xla_compiles": 303, "xla_compile_s": 9.75}},
}

# metric -> (the value by hand, what to take out of both documents so that
# there is nothing to read, the stages whose count stands still in a window
# in which they never ran — None where the metric has no such count)
CASES = {
    "turn_ms": (700.0, ("stages",), ("serve/service_time",)),
    "dispatcher_busy": (87.5, ("stages", "serve/idle"), None),
    "turn_assemble_ms": (50.0, ("stages",), ("serve/assemble",)),
    "turn_forward_ms": (25.0, ("stages",), ("serve/forward",)),
    "turn_readback_ms": (125.0, ("stages",), ("serve/readback",)),
    "turn_postprocess_ms": (500.0, ("stages",), ("serve/postprocess",)),
    "turn_post_decode_ms": (300.0, ("stages",), ("serve/post/decode",)),
    "turn_post_nms_ms": (150.0, ("stages",), ("serve/post/nms",)),
    "post_candidates_per_img": (16_000.0, ("counters", "post_candidates"),
                                "served"),
    "frontend_decode_ms": (20.0, ("stages",), ("frontend/decode",)),
    "host_prep_ms": (10.0, ("stages",), ("serve/host_prep",)),
    "xla_compiles_in_window": (3.0, ("compile",), None),
    "setup_build_s": (22.0, ("setup", "params_s"), None),
    "setup_warmup_s": (12.5, ("setup",), None),
    "setup_compile_s": (9.5, ("compile", "counters", "xla_compile_s"), None),
}


def read(name, before, after):
    one = dict(BENCH, per_layer=[m for m in BENCH["per_layer"]
                                 if m["name"] == name])
    assert len(one["per_layer"]) == 1, name
    ctx = {"metrics_before": before, "metrics_after": after}
    return harness.read_layers(one, CELL, ctx).get(name)


def without(doc, path):
    doc = copy.deepcopy(doc)
    node = doc
    for key in path[:-1]:
        node = node[key]
    del node[path[-1]]
    return doc


def test_every_stage_metric_has_a_case():
    ours = {m["name"] for m in BENCH["per_layer"]
            if m["source"].startswith("program_")} - {
                "queue_wait_ms", "batch_fill", "recompiles_in_window"}
    assert ours == set(CASES)
    for m in BENCH["per_layer"]:
        if m["name"] in CASES:
            assert m["workloads"] == ["c4-serve-open", "c4-serve-closed"]
            assert m["moves"] == ("setup_s" if m["name"].startswith("setup_")
                                  else "serve_imgs_per_s")


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_hand_made_window_gives_the_value_worked_out_by_hand(name):
    value = CASES[name][0]
    got = read(name, BEFORE, AFTER)
    assert got == {"value": pytest.approx(value), "unit": next(
        m["unit"] for m in BENCH["per_layer"] if m["name"] == name)}


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_document_without_the_key_gives_none(name):
    path = CASES[name][1]
    assert read(name, without(BEFORE, path), without(AFTER, path)) is None
    # and the documents of a program from before the stage clocks
    old = {"counters": {"batches": 3, "served": 9, "recompiles": 2}}
    assert read(name, old, dict(old, options={"batch_size": 16})) is None


@pytest.mark.parametrize("name", sorted(n for n, c in CASES.items() if c[2]))
def test_a_window_in_which_the_stage_never_ran_gives_none(name):
    still = CASES[name][2]
    after = copy.deepcopy(AFTER)
    if still == "served":
        after["counters"]["served"] = BEFORE["counters"]["served"]
    else:
        for stage in still:
            after["stages"][stage] = dict(BEFORE["stages"][stage])
    assert read(name, BEFORE, after) is None


def test_dispatcher_busy_is_its_own_clocks_and_needs_one_to_have_moved():
    # the instant of a snapshot does not enter: a late second one reads the same
    assert read("dispatcher_busy", BEFORE, dict(AFTER, t_s=150.0)) == read(
        "dispatcher_busy", BEFORE, AFTER)
    after = copy.deepcopy(AFTER)
    for stage in ("serve/service_time", "serve/idle"):
        after["stages"][stage] = dict(BEFORE["stages"][stage])
    assert read("dispatcher_busy", BEFORE, after) is None
    # a dispatcher that never waited was busy throughout
    after["stages"]["serve/service_time"] = AFTER["stages"]["serve/service_time"]
    assert read("dispatcher_busy", BEFORE, after)["value"] == 100.0
