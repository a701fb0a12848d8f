"""The plain-ViT trunk of ``--network vitdet_b_mask`` (PR 34) at a tiny
size on the CPU: the window cut, the decomposed relative terms, the blocked
attention kernel in interpret mode, the preset, one training step through
the one module train, eval and serve share, and the ResNet presets'
parameter trees, which the new choice of trunk must leave as they were.

The whole network against its plain reference is
``tests/benchmark_checks/test_vitdet_*.py``."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config, list_networks
from mx_rcnn_tpu.kernels import attention_pallas as ap
from mx_rcnn_tpu.models import build_model, init_params
from mx_rcnn_tpu.models.vit import window_partition, window_unpartition

TINY = dict(tpu__SCALES=((96, 96),), network__VIT_WIDTH=64,
            network__VIT_DEPTH=4, network__VIT_HEADS=2,
            network__VIT_WINDOW=4, network__VIT_GLOBAL_BLOCKS=(2,),
            TEST__RPN_PRE_NMS_TOP_N=500, TEST__RPN_POST_NMS_TOP_N=60)


def test_window_cut_and_paste_is_the_identity_on_the_unpadded_grid():
    """A 6 x 6 grid in windows of 4: padded to 8 x 8, four windows; every
    token lands in the window and place its coordinates say, the padding
    is zeros, and putting the windows back crops to the grid."""
    x = jnp.arange(2 * 6 * 6 * 3, dtype=jnp.float32).reshape(2, 6, 6, 3) + 1
    win, padded = window_partition(x, 4)
    assert win.shape == (2 * 4, 4, 4, 3) and padded == (8, 8)
    np.testing.assert_array_equal(win[0], x[0, :4, :4])
    np.testing.assert_array_equal(win[1, :, :2], x[0, :4, 4:6])
    np.testing.assert_array_equal(win[6, :2, :], x[1, 4:6, :4])
    assert not np.asarray(win[1, :, 2:]).any()      # the right padding
    assert not np.asarray(win[3, 2:, :]).any()      # the bottom padding
    np.testing.assert_array_equal(
        window_unpartition(win, 4, padded, (6, 6)), x)


@pytest.fixture(scope="module")
def qkv():
    s, d, g = 16, 64, 3
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v = (jax.random.normal(ks[i], (g, s * s, d), jnp.float32)
               for i in range(3))
    rel_h = 0.3 * jax.random.normal(ks[3], (2 * s - 1, d))
    rel_w = 0.3 * jax.random.normal(ks[4], (2 * s - 1, d))
    return s, d, q, k, v, rel_h, rel_w


def direct(q, k, v, rel_h, rel_w, s, d):
    """Equation 3 by a direct gather of both tables for every pair of
    tokens: Bh[(y,x),(y',x')] = q[y,x] . Rh[y - y' + S - 1]."""
    y, x = np.divmod(np.arange(s * s), s)
    bias = (jnp.einsum("gnd,nmd->gnm", q, rel_h[y[:, None] - y[None] + s - 1])
            + jnp.einsum("gnd,nmd->gnm", q,
                         rel_w[x[:, None] - x[None] + s - 1]))
    scores = jnp.einsum("gnd,gmd->gnm", q, k) * d ** -0.5 + bias
    return bias, jnp.einsum("gnm,gmd->gnd", jax.nn.softmax(scores, -1), v)


def test_decomposed_relative_terms_against_a_direct_gather(qkv):
    """``rel_terms`` gives (N, 2S) numbers a head; spread over the keys'
    rows and columns they are the (N, N) bias of the direct form, to a few
    float32 roundings of a sum of 64 products of unit spread."""
    s, d, q, k, v, rel_h, rel_w = qkv
    rel = ap.rel_terms(q, rel_h, rel_w, s)
    assert rel.shape == (3, s * s, 2 * s)
    bias, want = direct(q, k, v, rel_h, rel_w, s, d)
    spread = (rel[..., :s, None] + rel[..., None, s:]).reshape(bias.shape)
    np.testing.assert_allclose(spread, bias, atol=2e-5)
    assert float(jnp.std(bias)) > 1.0          # the terms matter here
    np.testing.assert_allclose(
        ap.attention_plain(q, k, v, rel, s, d ** -0.5), want, atol=2e-5)
    # the 0/1 rows the kernel stacks under k^T: one row and one column a key
    pos = ap.key_positions(s)
    assert pos.shape == (2 * s, s * s) and (pos.sum(0) == 2).all()
    assert pos[3, 3 * s:4 * s].all() and pos[s + 5, 5::s].all()


@pytest.mark.parametrize("dtype,atol", [("float32", 2e-5),
                                        ("bfloat16", 3e-2)])
def test_the_blocked_kernel_in_interpret_mode_against_plain_attention(
        qkv, dtype, atol):
    """Two q tiles and two key blocks a head, so the online softmax's
    rescaling and the once-a-head scratch are both exercised.  float32:
    the order of summation alone, a few roundings of outputs of unit
    spread.  bfloat16: the same inputs rounded to 8 bits of mantissa — the
    folded rows carry s.q and the relative terms at 2**-9 of a few units,
    the probabilities are rounded before the weighted sum on both sides —
    against the float32 direct form: 3e-2 of outputs that reach 3."""
    s, d, q, k, v, rel_h, rel_w = qkv
    _, want = direct(q, k, v, rel_h, rel_w, s, d)
    rel = ap.rel_terms(q, rel_h, rel_w, s)
    cast = lambda a: a.astype(dtype)  # noqa: E731
    got = ap.attention_blocked(cast(q), cast(k), cast(v), cast(rel), s,
                               d ** -0.5, block_q=128, block_k=128,
                               interpret=True)
    assert got.dtype == jnp.dtype(dtype) and got.shape == q.shape
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=atol)
    assert float(jnp.abs(want).max()) > 1.0


def test_off_the_chip_and_at_sizes_the_kernel_cannot_tile_the_plain_form(qkv):
    s, d, q, k, v, rel_h, rel_w = qkv
    assert jax.default_backend() != "tpu"
    _, want = direct(q, k, v, rel_h, rel_w, s, d)
    got = ap.attention(q[None], k[None], v[None], rel_h, rel_w, s, d ** -0.5)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    assert ap._fits(64 * 64, 64) and ap._fits(32 * 32, 32)
    assert not ap._fits(14 * 14, 14) and not ap._fits(6 * 6, 6)


def test_the_preset_is_one_square_bucket_without_host_s2d():
    assert "vitdet_b_mask" in list_networks()
    cfg = generate_config("vitdet_b_mask", "coco")
    net = cfg.network
    assert cfg.tpu.SCALES == ((1024, 1024),) and not net.HOST_S2D
    assert net.HOST_ROWS and not generate_config(
        "resnet101_fpn_mask", "coco").network.HOST_ROWS
    assert (net.NETWORK, net.HAS_FPN, net.HAS_MASK) == ("vit", True, True)
    assert (net.VIT_WIDTH, net.VIT_DEPTH, net.VIT_HEADS, net.VIT_WINDOW,
            net.VIT_PATCH, net.VIT_GLOBAL_BLOCKS) == (
        768, 12, 12, 14, 16, (2, 5, 8, 11))
    assert cfg.tpu.ROI_SAMPLING_RATIO == 2 and net.FPN_OUT_CHANNELS == 256
    # both orientations are the one bucket the engine keys programs by
    from mx_rcnn_tpu.data.image import bucket_shape
    assert bucket_shape(cfg.tpu.SCALES[0], 32, True) == \
        bucket_shape(cfg.tpu.SCALES[0], 32, False) == (1024, 1024)
    # a bucket that is not square is refused when the model is built
    bad = generate_config("vitdet_b_mask", "coco",
                          **dict(TINY, tpu__SCALES=((96, 128),)))
    with pytest.raises(AssertionError, match="one square bucket"):
        init_params(build_model(bad), bad, jax.random.PRNGKey(0))


def test_the_patch_embedding_is_the_convolution_on_either_input_form():
    """(B, H, W, 3) and the loader's row-flattened (B, H, W x 3) give the
    same patches, and the matmul over them is the 16 x 16 stride-16
    convolution whose kernel shape the parameter keeps."""
    from mx_rcnn_tpu.data.loader import prepare_image
    from mx_rcnn_tpu.models.vit import PatchEmbed

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 32, 48, 3)), jnp.float32)
    mod = PatchEmbed(8, 16, jnp.float32)
    params = mod.init(jax.random.PRNGKey(0), x)
    params = jax.tree.map(lambda a: a + 0.1, params)       # a bias too
    assert params["params"]["kernel"].shape == (16, 16, 3, 8)
    out = mod.apply(params, x)
    np.testing.assert_array_equal(out, mod.apply(params,
                                                 x.reshape(2, 32, 48 * 3)))
    want = jax.lax.conv_general_dilated(
        x, params["params"]["kernel"], (16, 16), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision="highest") + params["params"]["bias"]
    assert out.shape == (2, 2, 3, 8)
    np.testing.assert_allclose(out, want, atol=1e-4)
    cfg = generate_config("vitdet_b_mask", "coco", **TINY)
    image, info = prepare_image(rng.integers(0, 256, (50, 70, 3),
                                             dtype=np.uint8), cfg, (96, 96))
    assert image.shape == (96, 288) and info[1] == 96


def _tree(cfg, hw):
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: init_params(
        model, cfg, jax.random.PRNGKey(0), 1, hw))
    return {"/".join(str(k.key) for k in path): tuple(s.shape)
            for path, s in jax.tree_util.tree_flatten_with_path(shapes)[0]}


@pytest.mark.parametrize("network", ["resnet50_fpn", "resnet101_fpn_mask"])
def test_the_resnet_presets_parameter_trees_are_as_they_were(network):
    """Leaf for leaf what the benchmark's own description of the two
    ResNet pyramid detectors lists (``benchmark/fpn`` and ``benchmark/mask``
    ``leaf_specs``, written from their plain references): no leaf renamed,
    none added by the new RPN depth, head body or LayerNorm option."""
    from benchmark.fpn import weights as fpn_weights
    from benchmark.mask import weights as mask_weights

    cfg = generate_config(network, "coco", tpu__SCALES=((64, 96),))
    depth = cfg.network.NETWORK
    net = {"depth": depth, "num_classes": 81, "num_anchors": 3,
           "fpn_channels": 256, "head_hidden": 1024, "mask_channels": 256,
           "mask_convs": 4}
    want = (dict(mask_weights.leaf_specs(net)) if cfg.network.HAS_MASK
            else {p: s for p, s, _ in fpn_weights.leaf_specs(net)})
    assert _tree(cfg, (64, 96)) == want
    assert not any("norm" in k or "rpn_conv_3x3_" in k for k in want)


def test_one_training_step_reaches_every_leaf_of_the_trunk():
    """The same module, its train graph: a finite loss on a synthetic
    batch with one object, and a gradient that is not zero on every leaf
    of the trunk — position vectors, both relative tables of windowed and
    global blocks, every LayerNorm among them — and of the pyramid's P2
    path (at 96 px no anchor of P4 or above lies inside the image and every
    RoI pools on P2, so the upper levels hear nothing here)."""
    cfg = generate_config("vitdet_b_mask", "coco", **dict(
        TINY, TRAIN__RPN_PRE_NMS_TOP_N=200, TRAIN__RPN_POST_NMS_TOP_N=32,
        TRAIN__BATCH_ROIS=16, tpu__COMPUTE_DTYPE="float32",
        tpu__MAX_GT=4))
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    # the relative tables start at zero as the published model's do; give
    # them values so that their gradient is not an accident of the start
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.1 if "rel_pos" in str(path[-1]) else x, params)
    rng = np.random.default_rng(0)
    from mx_rcnn_tpu.data.mask import GT_MASK_SIZE
    batch = dict(
        images=jnp.asarray(rng.normal(size=(1, 96, 96, 3)), jnp.float32),
        im_info=jnp.asarray([[96, 96, 1.0]], jnp.float32),
        gt_boxes=jnp.asarray([[[10, 12, 60, 70]] + [[0] * 4] * 3],
                             jnp.float32),
        gt_classes=jnp.asarray([[7, 0, 0, 0]], jnp.int32),
        gt_valid=jnp.asarray([[True, False, False, False]]))
    masks = jnp.ones((1, 4, GT_MASK_SIZE, GT_MASK_SIZE), jnp.float32)

    def loss(p):
        total, aux = model.apply(
            {"params": p}, batch["images"], batch["im_info"],
            batch["gt_boxes"], batch["gt_classes"], batch["gt_valid"],
            jax.random.PRNGKey(1), gt_masks=masks)
        return total, aux

    (total, aux), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        params)
    assert np.isfinite(float(total)) and float(total) > 0
    assert "mask_loss" in aux and np.isfinite(float(aux["mask_loss"]))
    neck = {k: g for k, g in grads["neck"].items() if "2" in k}
    assert len(neck) == 7          # deconvs, norms, lateral and post of P2
    flat = jax.tree_util.tree_flatten_with_path(
        {"backbone": grads["backbone"], "neck": neck})[0]
    assert len(flat) == 4 * 14 + 3 + 12
    dead = [jax.tree_util.keystr(k) for k, g in flat
            if not (np.isfinite(np.asarray(g)).all()
                    and float(jnp.abs(g).max()) > 0)]
    assert not dead, dead


def test_the_backward_pass_of_the_kernel_path_is_the_plain_forms(qkv,
                                                                 monkeypatch):
    """On the chip the forward is the kernel and the backward
    differentiates the plain form: with the kernel run in interpret mode
    the gradients equal the plain form's own."""
    s, d, q, k, v, rel_h, rel_w = qkv
    rel = ap.rel_terms(q, rel_h, rel_w, s)
    real = ap.attention_blocked
    monkeypatch.setattr(ap, "attention_blocked",
                        lambda *a, **kw: real(*a, **kw, block_q=128,
                                              block_k=128, interpret=True))
    f = lambda fn: jax.grad(lambda *a: jnp.sum(  # noqa: E731
        fn(*a, s, d ** -0.5) ** 2), argnums=(0, 1, 2, 3))(q, k, v, rel)
    for got, want in zip(f(ap._attention_tpu), f(ap.attention_plain)):
        np.testing.assert_allclose(got, want, atol=1e-4)


def test_the_evaluator_scores_boxes_and_masks_of_the_vit_network(tmp_path):
    """``test.py``'s path (``eval/tester.py::pred_eval`` over a
    ``TestLoader``) on mini-COCO files: the loader's row-flattened images
    reach the trunk, the mask pass runs over the cached pyramid, and bbox
    and segm are scored (random weights — mechanics, not accuracy)."""
    from mx_rcnn_tpu.data import TestLoader
    from mx_rcnn_tpu.data.coco_dataset import COCODataset
    from mx_rcnn_tpu.eval import Predictor, pred_eval
    from mx_rcnn_tpu.train.checkpoint import denormalize_for_save
    from tests.fixtures import make_mini_coco

    make_mini_coco(str(tmp_path / "coco"), image_set="minitrain", n=2,
                   with_masks=True)
    cfg = generate_config("vitdet_b_mask", "coco", **dict(
        TINY, TEST__RPN_PRE_NMS_TOP_N=200, TEST__RPN_POST_NMS_TOP_N=16,
        TEST__MAX_PER_IMAGE=5, tpu__MAX_GT=8))
    imdb = COCODataset("minitrain", str(tmp_path / "data"),
                       str(tmp_path / "coco"))
    model = build_model(cfg)
    params = denormalize_for_save(
        init_params(model, cfg, jax.random.PRNGKey(0)), cfg)
    loader = TestLoader(imdb.gt_roidb(), cfg, batch_size=1)
    stats = pred_eval(Predictor(model, params, cfg), loader, imdb,
                      thresh=1e-3, with_masks=True)
    assert "bbox" in stats and "segm" in stats, stats
    assert "AP" in stats["segm"]
