"""A mask network served through the engine (PR 32): every record of every
answer carries the RLE the evaluator's mask pass writes for the same
detections; an image with no record is answered and still dispatches the
mask program at its one static shape; warm-up counts the mask program; a
network without a mask head runs nothing of the stage; the fused path
refuses a mask network; and ``native.paste_rle`` is safe from two threads
(ROADMAP D0's first half).

One tiny real model for the file (ResNet-50 body on the mask preset, a
64x96 bucket, 10 records an image, batch 2), warmed once.
"""

import dataclasses
import threading

import jax
import numpy as np
import pytest

from mx_rcnn_tpu import native
from mx_rcnn_tpu.compile.registry import xla_counters
from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.data import prepare_image
from mx_rcnn_tpu.eval import Predictor
from mx_rcnn_tpu.eval.mask_rle import decode
from mx_rcnn_tpu.eval.tester import _mask_pass, mask_to_rle, paste_mask
from mx_rcnn_tpu.models import build_model, init_params
from mx_rcnn_tpu.serve import ServeEngine, ServeOptions, warmup

from tests.test_serve import FakePredictor, tiny_cfg

CAP = 10


def mask_cfg(**test):
    cfg = generate_config(
        "resnet101_fpn_mask", "coco", TEST__RPN_PRE_NMS_TOP_N=250,
        TEST__RPN_POST_NMS_TOP_N=32, TEST__MAX_PER_IMAGE=CAP, **test)
    net = dataclasses.replace(cfg.network, NETWORK="resnet50",
                              PIXEL_STDS=(127.0, 127.0, 127.0))
    tpu = dataclasses.replace(cfg.tpu, SCALES=((64, 96),))
    return cfg.replace(network=net, tpu=tpu)


def wait_booked(engine):
    """A future resolves before its turn is booked (SKILL.md)."""
    with engine._cond:
        while engine._inflight:
            engine._cond.wait(timeout=0.05)


@pytest.fixture(scope="module")
def served():
    cfg = mask_cfg()
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0), 2, (64, 96))
    pred = Predictor(model, params, cfg)
    engine = ServeEngine(pred, cfg, ServeOptions(
        batch_size=2, max_delay_ms=5.0, max_queue=16)).start()
    compiled = warmup(engine)
    yield cfg, pred, engine, compiled
    engine.stop()


def test_warm_up_counts_the_mask_program_and_later_requests_compile_nothing(
        served):
    """Two programs an orientation: predict and the mask branch.  After
    warm-up ``recompiles == warmup_programs`` for good, and XLA builds or
    loads nothing for a later request of either orientation — its mask
    dispatch included."""
    cfg, pred, engine, compiled = served
    assert compiled == 4
    assert engine.counters["recompiles"] == 4 == \
        engine.counters["warmup_programs"]
    kinds = sorted(k.kind for k in pred.registry._seen)
    assert kinds.count("masks_from_feats") == 2       # one an orientation
    xla = xla_counters()["xla_compiles"]
    before = engine.counters["mask_dispatches"]
    rng = np.random.default_rng(3)
    for hw in ((50, 70), (70, 50), (40, 90)):
        recs = engine.submit(rng.integers(0, 256, hw + (3,),
                                          dtype=np.uint8)).result(120)
        assert recs and all("segmentation" in r for r in recs)
    wait_booked(engine)
    assert xla_counters()["xla_compiles"] == xla
    assert engine.counters["recompiles"] == 4
    assert engine.counters["mask_dispatches"] == before + 3


def test_every_record_carries_the_rle_the_evaluators_mask_pass_writes(served):
    """The same image in both rows of a batch, so that the offline forward
    below runs the same program on the same input: the served records'
    segmentations are, count for count, what ``_mask_pass`` writes for the
    same detections over the same pyramid."""
    cfg, pred, engine, _ = served
    img = np.random.default_rng(11).integers(0, 256, (52, 75, 3),
                                             dtype=np.uint8)
    futs = [engine.submit(img) for _ in range(2)]
    recs = [f.result(120) for f in futs]
    assert recs[0] == recs[1] and 0 < len(recs[0]) <= CAP
    wait_booked(engine)
    for r in recs[0]:
        seg = r["segmentation"]
        assert seg["size"] == [52, 75] and sum(seg["counts"]) == 52 * 75
        assert all(isinstance(c, int) for c in seg["counts"])

    prepared, im_info = prepare_image(img, cfg, cfg.tpu.SCALES[0])
    batch = {"images": np.stack([prepared, prepared]),
             "im_info": np.stack([im_info, im_info]), "indices": [0]}
    pred.predict(batch["images"], batch["im_info"])
    all_boxes = [[np.zeros((0, 5), np.float32)]
                 for _ in range(cfg.NUM_CLASSES)]
    order = {}
    for r in recs[0]:
        k = r["cls"]
        order[id(r)] = (k, len(all_boxes[k][0]))
        all_boxes[k][0] = np.concatenate([all_boxes[k][0], np.asarray(
            [r["bbox"] + [r["score"]]], np.float32)])
    all_masks = [[None] for _ in range(cfg.NUM_CLASSES)]
    _mask_pass(pred, batch, [None], all_boxes, all_masks,
               [{"height": 52, "width": 75}], CAP, cfg.NUM_CLASSES,
               token=pred.feats_token)
    for r in recs[0]:
        k, di = order[id(r)]
        assert all_masks[k][0][di] == r["segmentation"]
    # and the masks are masks: inside their windows, not all alike
    areas = {int(decode(r["segmentation"]).sum()) for r in recs[0]}
    assert len(areas) > 1


def test_an_image_with_no_record_is_answered_and_dispatches_the_mask_program(
        served):
    """A second engine over the same predictor whose score threshold no
    candidate passes: the answer is an empty list, and the turn still ran
    the mask program at its one shape — first seen in warm-up, so nothing
    compiles."""
    cfg, pred, _, _ = served
    strict = cfg.replace(TEST=dataclasses.replace(cfg.TEST, THRESH=0.9999))
    engine = ServeEngine(pred, strict, ServeOptions(
        batch_size=2, max_delay_ms=1.0, max_queue=4)).start()
    try:
        xla = xla_counters()["xla_compiles"]
        img = np.random.default_rng(5).integers(0, 256, (50, 70, 3),
                                                dtype=np.uint8)
        assert engine.submit(img).result(120) == []
        wait_booked(engine)
        c = engine.counters
        assert (c["mask_dispatches"], c["mask_rois"], c["mask_native"]) == \
            (1, 0, 0)
        assert c["mask_readback_bytes"] == 2 * CAP * 28 * 28 * 4
        assert c["dispatches"] == 2 and c["readbacks"] == 2
        assert c["recompiles"] == 0      # the predictor had seen both
        assert xla_counters()["xla_compiles"] == xla
    finally:
        engine.stop()


def test_the_mask_stage_is_on_metrics_and_accounts_for_its_parts(served):
    cfg, pred, engine, _ = served
    engine.submit(np.zeros((50, 70, 3), np.uint8)).result(120)
    wait_booked(engine)
    m = engine.metrics()
    stages, c = m["stages"], m["counters"]
    names = ("serve/mask", "serve/mask/forward", "serve/mask/readback",
             "serve/mask/paste")
    assert all(stages[n]["count"] == c["batches"] for n in names)
    parts = sum(stages[n]["sum_s"] for n in names[1:])
    assert parts <= stages["serve/mask"]["sum_s"] <= 1.1 * parts + 0.05
    # the turn covers both stages; the post-process clock is its own
    assert stages["serve/service_time"]["sum_s"] > \
        stages["serve/mask"]["sum_s"] + stages["serve/postprocess"]["sum_s"]
    assert c["mask_dispatches"] == c["batches"]
    assert c["mask_rois"] == c["post_kept"]
    assert c["mask_native"] == (c["mask_rois"]
                                if native.available("mxr_paste_rle") else 0)
    assert c["mask_readback_bytes"] == c["batches"] * 2 * CAP * 28 * 28 * 4


def test_the_host_paste_is_the_fallback_and_gives_the_same_masks(served):
    """``TEST.MASK_PASTE = "host"`` (and a machine without the library):
    cv2 paste + numpy RLE through the same function; within the 3 pixels a
    mask that ``test_mask_pass_modes_agree`` allows."""
    cfg, pred, engine, _ = served
    host = cfg.replace(TEST=dataclasses.replace(cfg.TEST, MASK_PASTE="host"))
    other = ServeEngine(pred, host, ServeOptions(
        batch_size=2, max_delay_ms=1.0, max_queue=4)).start()
    try:
        img = np.random.default_rng(17).integers(0, 256, (60, 44, 3),
                                                 dtype=np.uint8)
        a = [engine.submit(img) for _ in range(2)][0].result(120)
        b = [other.submit(img) for _ in range(2)][0].result(120)
        wait_booked(other)
        assert other.counters["mask_native"] == 0 < \
            other.counters["mask_rois"]
        assert [r["bbox"] for r in a] == [r["bbox"] for r in b]
        for ra, rb in zip(a, b):
            assert np.sum(decode(ra["segmentation"])
                          != decode(rb["segmentation"])) <= 3
    finally:
        other.stop()


def test_a_network_without_a_mask_head_runs_nothing_of_the_stage(
        monkeypatch):
    """Its turn never enters ``_mask_stage``, its ``/metrics`` has no
    ``serve/mask`` clock and no mask counter, its records no new key."""
    cfg = tiny_cfg()
    monkeypatch.setattr(ServeEngine, "_mask_stage",
                        lambda *a, **k: pytest.fail("the mask stage ran"))
    engine = ServeEngine(FakePredictor(cfg), cfg, ServeOptions(
        batch_size=2, max_delay_ms=1.0, max_queue=4)).start()
    try:
        recs = engine.submit(np.full((60, 100, 3), 90, np.uint8)).result(30)
        wait_booked(engine)
        assert recs and set(recs[0]) == {"cls", "score", "bbox"}
        m = engine.metrics()
        assert not [n for n in m["stages"] if n.startswith("serve/mask")]
        assert not [n for n in m["counters"] if n.startswith("mask_")]
        assert m["counters"]["dispatches"] == m["counters"]["batches"] == 1
    finally:
        engine.stop()


def test_the_fused_path_refuses_a_mask_network_at_start_up():
    cfg = mask_cfg()
    with pytest.raises(ValueError, match="--serve-e2e cannot serve a mask"):
        ServeEngine(FakePredictor(cfg), cfg, ServeOptions(serve_e2e=True))
    # without the flag the same pair builds
    ServeEngine(FakePredictor(cfg), cfg, ServeOptions())


@pytest.mark.skipif(not native.available("mxr_paste_rle"),
                    reason="no native library here")
def test_paste_rle_from_two_threads_gives_the_single_threads_rles():
    """ROADMAP D0: ctypes lets go of the GIL inside the call, and the
    encoder's count buffer was one module global.  64 masks, pasted by one
    thread, then by two at once: every RLE the same."""
    rng = np.random.default_rng(0)
    work = []
    for _ in range(64):
        x1, y1 = rng.uniform(-20, 300), rng.uniform(-20, 200)
        box = np.asarray([x1, y1, x1 + rng.uniform(5, 250),
                          y1 + rng.uniform(5, 200)], np.float32)
        work.append((rng.random((28, 28), dtype=np.float32), box))
    single = [native.paste_rle(p, b, 240, 320) for p, b in work]
    assert all(sum(c) == 240 * 320 for c in single)
    got = [[None] * 64 for _ in range(2)]
    start = threading.Barrier(2)

    def run(t):
        start.wait()
        for _ in range(5):
            for i, (p, b) in enumerate(work):
                got[t][i] = native.paste_rle(p, b, 240, 320)

    threads = [threading.Thread(target=run, args=(t,)) for t in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert got[0] == single and got[1] == single
    # the shared function's two ways agree with each other
    p, b = work[0]
    assert np.sum(decode(mask_to_rle(p, b, 240, 320))
                  != paste_mask(p, b, 240, 320)) <= 3
    assert mask_to_rle(p, b, 240, 320, native=False)["size"] == [240, 320]
