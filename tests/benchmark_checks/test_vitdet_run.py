"""``vitdet-b-mask``'s comparison on the program as it serves, at the tiny
size: the benchmark's seeded weights in the program's own engine
(``ServeEngine`` over ``Predictor``, bfloat16, staging batches, two in
flight, the mask stage — in this process, no socket: ``test_mask_run.py``
pays a minute for the HTTP path and this network adds nothing to it), the
cell's request bodies, the plain reference over them with its mask branch
run at the served boxes, the configuration's comparison and limits; the
driver's half after the window on the same answers, sound and altered; and
the float8 control, which has to read not correct.

CPU readings at this size (seed 2**31 + 3; float8 control, seeds 1-2):
``box_gap`` 0.0045 against 0.042-0.044, ``score_gap`` 0.019 against
0.24-0.25, ``mask_gap`` 0.024 against 0.222-0.229, ``mask_firm_faults`` at a
margin of 0.02 0.0013 against 0.089; ``mask_fill`` 0.48."""

import copy
import json

import jax
import pytest

from benchmark import harness, loadgen
from benchmark.drivers import serve as drv
from benchmark.reference import frcnn_c4
from benchmark.weights import as_tree, check_against

from . import tiny_vitdet

SEED = 2 ** 31 + 3


@pytest.fixture(scope="module")
def served():
    from mx_rcnn_tpu.eval.tester import Predictor
    from mx_rcnn_tpu.models.detector import build_model, init_params
    from mx_rcnn_tpu.serve import ServeEngine, ServeOptions, warmup

    spec = tiny_vitdet.tiny_spec()
    config = spec["config"]
    mods = harness.modules_of(config)
    flat = mods["weights"].make(config["net"], SEED)
    cfg = tiny_vitdet.program_cfg()
    assert cfg.tpu.COMPUTE_DTYPE == "bfloat16"
    model = build_model(cfg)
    check_against(flat, jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0))))
    engine = ServeEngine(Predictor(model, as_tree(flat), cfg), cfg,
                         ServeOptions(batch_size=2, max_delay_ms=5.0,
                                      max_queue=16)).start()
    compiled = warmup(engine)
    bodies = loadgen.make_bodies(spec["traffic"]["bodies"], SEED)
    docs = [json.loads(b) for b in bodies[:4]]
    assert {d["shape"][0] > d["shape"][1] for d in docs} == {True, False}
    futs = [engine.submit(frcnn_c4.decode_body(d)) for d in docs]
    sample = []
    for d, f in zip(docs, futs):
        recs = f.result(300)
        sample.append({"doc": d, "detections": recs,
                       "response": {"detections": recs}})
    with engine._cond:
        while engine._inflight:
            engine._cond.wait(timeout=0.05)
    counters = dict(engine.counters)
    engine.stop()
    res = {"sample": sample, "attempted": len(sample), "failed": 0}
    return spec, mods, flat, res, compiled, counters


def test_both_orientations_are_one_bucket_and_warm_up_builds_two_programs(
        served):
    """A square scale: landscape and portrait share the one 96 x 96 bucket,
    so warm-up dispatches predict and the mask branch once each — not two
    an orientation — and the portrait and landscape requests after it
    compile nothing."""
    _, _, _, _, compiled, counters = served
    assert compiled == 2
    assert counters["recompiles"] == counters["warmup_programs"] == 2
    assert counters["mask_dispatches"] == counters["batches"] >= 3


def test_the_served_answers_are_correct_by_the_cells_comparison(served):
    spec, mods, flat, res, _, _ = served
    config = spec["config"]
    line, compared = drv.after_window(
        mods, config, flat, res, {"setup_s": {"value": 1.0, "unit": "s"}},
        {"platform": "cpu", "kind": "cpu", "count": 1})
    doc = json.loads(line)
    assert doc["correct"] is True, doc["compared"]
    assert set(doc["compared"]) == set(config["correct"])
    c = {k: v["value"] for k, v in doc["compared"].items()}
    assert c["masks"] == c["records"] == 4 * 12     # a mask on every record
    assert c["mask_missing"] == 0 and c["mask_firm_faults"] < 4e-3
    assert c["box_gap"] < 0.015 and c["score_gap"] < 0.05
    assert c["mask_gap"] < 0.06 and 0.2 < c["mask_fill"] < 0.8
    for s in res["sample"]:
        h, w = s["doc"]["shape"][:2]
        assert all(r["segmentation"]["size"] == [h, w]
                   for r in s["detections"])


@pytest.mark.parametrize("fault", ["swapped", "dropped", "shifted"])
def test_altered_answers_are_not_correct(served, fault):
    """Every record answering its neighbour's mask; one mask dropped; every
    box moved by a fifth of its width."""
    spec, mods, flat, res, _, _ = served
    res = copy.deepcopy(res)
    for s in res["sample"]:
        recs = s["detections"]
        segs = [r["segmentation"] for r in recs]
        if fault == "swapped":
            for r, seg in zip(recs, segs[1:] + segs[:1]):
                r["segmentation"] = seg
        elif fault == "dropped":
            del recs[0]["segmentation"]
        else:
            for r in recs:
                x0, y0, x1, y1 = r["bbox"]
                dx = 0.2 * (x1 - x0)
                r["bbox"] = [x0 + dx, y0, x1 + dx, y1]
    line, compared = drv.after_window(
        mods, spec["config"], flat, res,
        {"setup_s": {"value": 1.0, "unit": "s"}},
        {"platform": "cpu", "kind": "cpu", "count": 1})
    assert json.loads(line)["correct"] is False
    if fault == "dropped":
        assert compared["mask_missing"] == (float(len(res["sample"])), 0)
    elif fault == "shifted":
        assert compared["box_gap"][0] > compared["box_gap"][1]


@pytest.mark.parametrize("seed", [1, 2])
def test_the_float8_control_is_not_correct(seed):
    spec = tiny_vitdet.tiny_spec()
    mods = harness.modules_of(spec["config"])
    numbers = mods["control"].control_numbers(spec["config"], spec["traffic"],
                                              seed, bodies=3)
    ok, compared = mods["compare"].judge(numbers, spec["config"]["correct"])
    assert ok is False
    failing = [k for k, (v, lim) in compared.items()
               if k in ("box_gap", "score_gap", "mask_gap",
                        "mask_firm_faults") and v > lim]
    assert len(failing) >= 2, compared
    assert numbers["mask_missing"] == 0 and numbers["masks"] >= 36
    if seed == 1:
        # the same pipeline in float32 is the reference itself: all zeros
        exact = mods["control"].control_numbers(
            spec["config"], spec["traffic"], seed, bodies=2,
            precision="f32")
        assert exact["mask_gap"] == exact["mask_firm_faults"] == 0.0
        assert exact["box_gap"] == 0.0
        limits = dict(spec["config"]["correct"], records=20, masks=20)
        assert mods["compare"].judge(exact, limits)[0] is True
