"""The host box decode (``ops.postprocess.decode_image_boxes``) against its
in-graph twin (``ops.boxes.bbox_pred`` + ``clip_boxes``), and the proof that
it stays on the host: no transfer, no trace, no program (CPU, small sizes).
Then ``per_class_nms``: the one native call an image against the loop it
replaced, row for row, and the proof that it is one call.
"""

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from mx_rcnn_tpu import native
from mx_rcnn_tpu.compile.registry import ProgramRegistry, xla_counters
from mx_rcnn_tpu.ops.boxes import bbox_pred, clip_boxes
from mx_rcnn_tpu.ops.nms import nms as numpy_nms
from mx_rcnn_tpu.ops.postprocess import decode_image_boxes, per_class_nms

# (eh, ew, scale): what the loader ships for a 375x500 and a 500x375 image
LANDSCAPE = (600.0, 800.0, 1.6)
PORTRAIT = (800.0, 600.0, 1.6)


@jax.jit
def in_graph(rois, deltas, im_info):
    """What ``device_postprocess`` computes an image, and what the host path
    computed before it had a function of its own."""
    boxes = clip_boxes(bbox_pred(rois, deltas), im_info[0], im_info[1])
    return boxes / im_info[2]


def seeded_inputs(seed, R, K, im_info, dtype):
    """RoIs that cross every border of the (eh, ew) frame (the first four
    rows cross one each, whatever R), class-specific deltas with dx / dy in
    ±1 and dw / dh up to ±4 (the ends included)."""
    eh, ew, _ = im_info
    rng = np.random.RandomState(seed)
    x1 = rng.uniform(-60.0, ew + 20.0, (R, 1))
    y1 = rng.uniform(-60.0, eh + 20.0, (R, 1))
    rois = np.concatenate([x1, y1, x1 + rng.uniform(0.0, 400.0, (R, 1)),
                           y1 + rng.uniform(0.0, 400.0, (R, 1))], axis=1)
    rois[:4] = [[-30.0, 100.0, 50.0, 180.0],            # left
                [100.0, -30.0, 180.0, 50.0],            # top
                [ew - 40.0, 100.0, ew + 35.0, 180.0],   # right
                [100.0, eh - 40.0, 180.0, eh + 35.0]]   # bottom
    deltas = rng.uniform(-1.0, 1.0, (R, K, 4))
    deltas[:, :, 2:] = rng.uniform(-4.0, 4.0, (R, K, 2))
    deltas[0, :, 2:] = 4.0
    deltas[1, :, 2:] = -4.0
    return (rois.astype(np.float32),
            deltas.reshape(R, 4 * K).astype(dtype),
            np.asarray(im_info, np.float32))


@pytest.mark.parametrize("im_info", [LANDSCAPE, PORTRAIT],
                         ids=["landscape", "portrait"])
@pytest.mark.parametrize("dtype", [np.float32, ml_dtypes.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("R,K", [(300, 81), (7, 2)],
                         ids=["R300-K81", "R7-K2"])
def test_host_decode_matches_the_in_graph_pair(R, K, dtype, im_info):
    rois, deltas, info = seeded_inputs(1000 * R + K, R, K, im_info, dtype)
    got = decode_image_boxes(rois, deltas, info)
    assert isinstance(got, np.ndarray) and got.dtype == np.float32
    assert got.shape == (R, 4 * K) and got.flags.c_contiguous
    expect = np.asarray(in_graph(rois, deltas, info))
    assert expect.dtype == np.float32  # jnp's promotion: bfloat16 cast up
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-3)
    # the inputs did what they were made for: boxes pinned to every border
    # of the frame, and boxes strictly inside it
    eh, ew, s = info
    x1, y1, x2, y2 = (got[:, i::4] for i in range(4))
    assert (x1 == 0).any() and (y1 == 0).any()
    assert (x2 == (ew - 1) / s).any() and (y2 == (eh - 1) / s).any()
    assert ((x1 > 0) & (x2 < (ew - 1) / s)).any()


@pytest.mark.parametrize("row", ["array", "tuple"])
def test_host_decode_stays_on_the_host(row):
    """numpy in, numpy out, and jax sees nothing of it: no host-to-device
    transfer (the guard raises on one), no trace and no program (the
    ``jax.monitoring`` counters behind ``/metrics["compile"]``).  Shapes no
    other test uses, so a ``jnp`` helper that crept back in would have to
    compile here."""
    ProgramRegistry(None)  # any registry starts the listeners
    rois, deltas, info = seeded_inputs(5, 13, 5, LANDSCAPE, np.float32)
    if row == "tuple":  # what a caller without the loader's array passes
        info = tuple(float(v) for v in info)
    before = xla_counters()
    with jax.transfer_guard("disallow"):
        boxes = decode_image_boxes(rois, deltas, info)
        assert xla_counters() == before
        # the guard bites on this backend: the in-graph twin, handed the
        # same numpy arrays, is refused their transfer
        with pytest.raises(Exception, match="[Dd]isallowed.*transfer"):
            bbox_pred(rois, deltas)
    assert type(boxes) is np.ndarray and boxes.shape == (13, 20)
    np.testing.assert_allclose(
        boxes, np.asarray(in_graph(rois, deltas, jnp.asarray(info))),
        rtol=1e-5, atol=1e-3)


# -- per_class_nms: one native call an image -------------------------------

THRESH, NMS_THRESH = 1e-3, 0.3


def nms_inputs(R, K, share, seed=0):
    """(R, K) float32 scores of which ``share`` lie over THRESH, (R, 4K)
    boxes 4-204 px wide around centres in a 600 px frame, every row valid:
    the serving cells' shapes and candidate counts."""
    rng = np.random.default_rng(seed)
    scores = np.where(rng.random((R, K)) < share,
                      rng.random((R, K), dtype=np.float32),
                      np.float32(1e-4)).astype(np.float32)
    ctr = rng.random((R, K, 2), dtype=np.float32) * np.float32(600)
    wh = rng.random((R, K, 2), dtype=np.float32) * np.float32(200) + 4
    boxes = np.concatenate([ctr - wh / 2, ctr + wh / 2], -1)
    return scores, boxes.reshape(R, 4 * K), np.ones(R, bool)


def _c4():
    return nms_inputs(300, 81, 0.456)          # ~11,000 candidates


def _fpn():
    return nms_inputs(1000, 81, 0.3)           # ~24,000


def _voc():
    return nms_inputs(300, 21, 0.45)


def _tied_scores():
    """Scores on a grid of 1/64: exact ties inside every class (the stable
    order decides who is kept) and across classes (a cap falls on one)."""
    scores, boxes, valid = nms_inputs(300, 21, 0.45, seed=1)
    return np.round(scores * 64) / np.float32(64), boxes, valid


def _invalid_rows():
    scores, boxes, valid = nms_inputs(300, 21, 0.45, seed=2)
    valid[::3] = False
    valid[-5:] = False
    return scores, boxes, valid


def _empty_classes():
    scores, boxes, valid = nms_inputs(120, 21, 0.45, seed=3)
    scores[:, [1, 7, 8, 20]] = 0.0             # first, a pair, last
    return scores, boxes, valid


def _no_candidate():
    scores, boxes, valid = nms_inputs(50, 21, 0.45, seed=4)
    return np.minimum(scores, np.float32(THRESH)), boxes, valid


def _touching():
    """Class 1: 10 px boxes side by side whose legacy width of overlap is
    exactly 0 (x1 of one = x2 of the last + 1: ``iw`` = 0, all kept), then
    the same row again one pixel closer (``iw`` = 1, IoU 1/19: kept at 0.3);
    class 2: exact duplicates (IoU 1: one kept)."""
    R, K = 12, 3
    scores = np.zeros((R, K), np.float32)
    boxes = np.zeros((R, 4 * K), np.float32)
    for i in range(R):
        step = 10 if i < 6 else 9
        x = (i % 6) * step
        boxes[i, 4:8] = [x, 100 * (i // 6), x + 9, 100 * (i // 6) + 9]
        boxes[i, 8:12] = [5, 5, 50, 50]
        scores[i, 1] = 0.9 - 0.01 * i
        scores[i, 2] = 0.5 + 0.01 * i
    return scores, boxes, np.ones(R, bool)


NMS_CASES = {"c4-300x81": _c4, "fpn-1000x81": _fpn, "voc-300x21": _voc,
             "tied-scores": _tied_scores, "invalid-rows": _invalid_rows,
             "empty-classes": _empty_classes, "no-candidate": _no_candidate,
             "touching": _touching}
# the numpy oracle sorts with argsort()[::-1], which is no stable order
TIE_FREE = sorted(set(NMS_CASES) - {"tied-scores", "touching"})


def assert_same_lists(got, want, K):
    assert len(got) == len(want) == K and got[0] is None and want[0] is None
    for k in range(1, K):
        assert got[k].dtype == want[k].dtype == np.float32, k
        assert got[k].shape == want[k].shape and got[k].shape[1:] == (5,), k
        assert got[k].flags.c_contiguous, k
        assert np.array_equal(got[k], want[k]), k


@pytest.mark.parametrize("cap", [0, 100])
@pytest.mark.parametrize("case", sorted(NMS_CASES))
def test_native_per_class_nms_is_the_native_loop_row_for_row(case, cap):
    """The one call against the loop it replaced (a ``native.nms`` call a
    class), bit for bit, class by class."""
    assert native.available("mxr_nms_classes")
    scores, boxes, valid = NMS_CASES[case]()
    K = scores.shape[1]
    got = per_class_nms(scores, boxes, valid, K, THRESH, NMS_THRESH, cap)
    want = per_class_nms(scores, boxes, valid, K, THRESH, NMS_THRESH, cap,
                         nms_fn=native.nms)
    assert_same_lists(got, want, K)
    kept = sum(len(d) for d in got[1:])
    candidates = int(((scores[:, 1:] > THRESH) & valid[:, None]).sum())
    if case == "c4-300x81":
        assert 10_500 < candidates < 11_500
    elif case == "fpn-1000x81":
        assert 23_000 < candidates < 25_000
    elif case == "no-candidate":
        assert candidates == kept == 0
    elif case == "empty-classes":
        assert [len(got[k]) for k in (1, 7, 8, 20)] == [0] * 4 < [kept]
    elif case == "touching":
        assert [len(d) for d in got[1:]] == [12, 1]
    elif case == "tied-scores":
        ties = max(len(d) - len(np.unique(d[:, 4])) for d in got[1:])
        assert ties > 0             # the stable order had something to decide
        if cap:                     # ">= th" keeps every row tied at the cut
            assert kept > cap
    if cap and case not in ("tied-scores", "no-candidate", "touching"):
        assert kept == cap


@pytest.mark.parametrize("cap", [0, 100])
@pytest.mark.parametrize("case", TIE_FREE)
def test_native_per_class_nms_matches_the_numpy_oracle(case, cap):
    scores, boxes, valid = NMS_CASES[case]()
    K = scores.shape[1]
    got = per_class_nms(scores, boxes, valid, K, THRESH, NMS_THRESH, cap)
    want = per_class_nms(scores, boxes, valid, K, THRESH, NMS_THRESH, cap,
                         nms_fn=numpy_nms)
    assert_same_lists(got, want, K)


def test_a_cap_on_tied_scores_keeps_both():
    """Two classes, four far-apart boxes, scores 0.75 / 0.5 / 0.5 / 0.25: a cap
    of 2 cuts at 0.5 and keeps three rows, on both paths."""
    scores = np.zeros((4, 3), np.float32)
    scores[:, 1] = [0.75, 0.5, 0.0, 0.0]
    scores[:, 2] = [0.0, 0.0, 0.5, 0.25]
    boxes = np.zeros((4, 12), np.float32)
    for i in range(4):
        boxes[i] = np.tile([100 * i, 0, 100 * i + 20, 20], 3)
    valid = np.ones(4, bool)
    got = per_class_nms(scores, boxes, valid, 3, THRESH, NMS_THRESH, 2)
    assert [d[:, 4].tolist() for d in got[1:]] == [[0.75, 0.5], [0.5]]
    assert_same_lists(got, per_class_nms(scores, boxes, valid, 3, THRESH,
                                         NMS_THRESH, 2, nms_fn=numpy_nms), 3)


@pytest.mark.parametrize("dtype", [np.float64, ml_dtypes.bfloat16],
                         ids=["float64", "bfloat16"])
def test_native_per_class_nms_casts_to_float32_first(dtype):
    """Scores and boxes of another dtype give the rows of their float32
    copies (what the loop's ``astype`` keeps)."""
    scores, boxes, valid = _voc()
    scores, boxes = scores.astype(dtype), boxes.astype(dtype)
    got = per_class_nms(scores, boxes, valid, 21, THRESH, NMS_THRESH, 100)
    want = per_class_nms(scores.astype(np.float32), boxes.astype(np.float32),
                         valid, 21, THRESH, NMS_THRESH, 100,
                         nms_fn=native.nms)
    assert_same_lists(got, want, 21)


class CountingLib:
    """The loaded library with every foreign call counted by name."""

    def __init__(self, lib):
        self._lib, self.calls = lib, {}

    def __getattr__(self, name):
        fn = getattr(self._lib, name)

        def counted(*args):
            self.calls[name] = self.calls.get(name, 0) + 1
            return fn(*args)
        return counted


def test_per_class_nms_crosses_into_the_library_once(monkeypatch):
    """One image = one foreign call, whatever K; ``native.nms`` is never
    called.  Without the library the same inputs give the same lists
    through the loop."""
    scores, boxes, valid = _c4()
    assert native.available("mxr_nms_classes")
    lib = CountingLib(native._load())
    monkeypatch.setattr(native, "_lib", lib)
    real_nms, nms_calls = native.nms, []
    monkeypatch.setattr(native, "nms",
                        lambda *a: nms_calls.append(1) or real_nms(*a))
    got = per_class_nms(scores, boxes, valid, 81, THRESH, NMS_THRESH, 100)
    assert lib.calls == {"mxr_nms_classes": 1} and not nms_calls

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load", lambda: None)
    assert not native.available() and not native.available("mxr_nms_classes")
    assert native.nms_classes(scores, boxes, valid, THRESH, NMS_THRESH) is None
    fallback = per_class_nms(scores, boxes, valid, 81, THRESH, NMS_THRESH, 100)
    assert len(nms_calls) == 80     # a call a class, each on the numpy NMS
    assert_same_lists(got, fallback, 81)


def test_nms_classes_refuses_mismatched_shapes():
    scores, boxes, valid = _voc()
    with pytest.raises(AssertionError, match=r"\(R, 4K\) boxes"):
        native.nms_classes(scores, boxes[:, :-4], valid, THRESH, NMS_THRESH)
    with pytest.raises(AssertionError, match=r"\(R,\) valid"):
        native.nms_classes(scores, boxes, valid[:-1], THRESH, NMS_THRESH)
