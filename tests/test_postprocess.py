"""The host box decode (``ops.postprocess.decode_image_boxes``) against its
in-graph twin (``ops.boxes.bbox_pred`` + ``clip_boxes``), and the proof that
it stays on the host: no transfer, no trace, no program (CPU, small sizes).
"""

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from mx_rcnn_tpu.compile.registry import ProgramRegistry, xla_counters
from mx_rcnn_tpu.ops.boxes import bbox_pred, clip_boxes
from mx_rcnn_tpu.ops.postprocess import decode_image_boxes

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
