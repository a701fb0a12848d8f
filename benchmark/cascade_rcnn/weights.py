"""``r101-fpn-cascade-mask``'s weights from the seed: the ``weights`` row of
``benchmark/README.md``, "A configuration" (``make(net, seed)``; ``as_tree``
and ``check_against`` stay in ``benchmark/weights.py`` for every
configuration).

``benchmark.mask.weights.make`` as it is — ``r101-fpn-mask``'s trunk, neck,
RPN, stage-1 head body and class output and mask head, the same base
network for every seed with its hidden channels in a seeded order — with
stage 1's box output cut to one box a RoI, and the box heads of stages 2
and 3 (``reference.cmrcnn_fpn.conv_layers``, part "stage_fc") drawn the
same way: values once from ``BASE_SEED``, the seed permuting each hidden
width (fc6's outputs with its bias and fc7's inputs; fc7's outputs with its
bias and both output layers' inputs), in one jitted call on the device,
float32.

**The box outputs.**  Each stage's four raw deltas are drawn at a spread
of 1 / w_t of unit normalised deltas: stage t's output layer moves a box's
centre by about 10 / 5 / 3.3 % of its size and its size by about 20 / 10 /
6.7 % in log (w_t = (10, 10, 5, 5), (20, 20, 10, 10), (30, 30, 15, 15), the
targets' published spreads), so later stages move boxes less, as trained
cascades do (Cai & Vasconcelos, figure 2).  Stage 1's layer is
``r101-fpn``'s class-1 columns, rescaled: they are already in the seed's
order.  The class outputs of stages 2 and 3 are drawn independently of
stage 1's, at ``r101-fpn``'s gain, so the averaged scores differ from stage
1's alone by far more than rounding (the comparison reads that gap).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.fpn import weights as fpn_weights
from benchmark.mask import weights as mask_weights
from benchmark.reference import cmrcnn_fpn
from benchmark.weights import seed_key

BASE_SEED = fpn_weights.BASE_SEED
NETWORK = "resnet101_fpn_cascade_mask"
# a raw delta's spread is BOX_GAIN / w_t[i] (the fpn detector's gain of
# 0.07 gives deltas of about a tenth: BOX_GAIN / 10)
BOX_GAIN = 10.0 * fpn_weights.GAINS["rcnn_out/bbox_pred"]
CLS_GAIN = fpn_weights.GAINS["rcnn_out/cls_score"]


def box_gains(net: dict, t: int) -> np.ndarray:
    return BOX_GAIN / np.asarray(net["stage_weights"][t - 1], np.float64)


def layers_of(net: dict):
    return cmrcnn_fpn.conv_layers(net["depth"], net["num_classes"],
                                  net["num_anchors"], net["fpn_channels"],
                                  net["head_hidden"], net["cascade_stages"])


def stage_leaf_specs(net: dict):
    """[(path, shape, std)] of stages 2..'s box heads, in a fixed order;
    std is a (cout,) column spread for a box output."""
    out = []
    for (path, _kh, _kw, cin, cout, _s, _bn, _b, part) in layers_of(net):
        if part != "stage_fc":
            continue
        t = int(path.split("/")[0][-1])
        if path.endswith("bbox_pred"):
            std = box_gains(net, t) / cin ** 0.5
            bias = 0.1 * box_gains(net, t)
        else:
            gain = CLS_GAIN if path.endswith("cls_score") else 2.0 ** 0.5
            std, bias = np.full(cout, gain / cin ** 0.5), np.full(cout, 0.02)
        out.append((f"{path}/kernel", (cin, cout), std))
        out.append((f"{path}/bias", (cout,), bias))
    return out


def leaf_specs(net: dict):
    """[(path, shape)] of every parameter of the configuration."""
    pyramid = [(p, s) for p, s, _ in fpn_weights.leaf_specs(net)
               if not p.startswith("rcnn_out/bbox_pred/")]
    hidden = net["head_hidden"]
    return (pyramid + [("rcnn_out/bbox_pred/kernel", (hidden, 4)),
                       ("rcnn_out/bbox_pred/bias", (4,))]
            + [(p, s) for p, s, _ in stage_leaf_specs(net)]
            + [(p, s) for p, s, _ in mask_weights.mask_leaf_specs(net)])


def channel_groups(net: dict):
    """[(width, [(leaf path, axis), ...])]: the two hidden widths of each
    later stage's head."""
    h = net["head_hidden"]
    out = []
    for t in range(2, net["cascade_stages"] + 1):
        body, head = cmrcnn_fpn.stage_names(t)
        out.append((h, [(f"{body}/fc6/kernel", 1), (f"{body}/fc6/bias", 0),
                        (f"{body}/fc7/kernel", 0)]))
        out.append((h, [(f"{body}/fc7/kernel", 1), (f"{body}/fc7/bias", 0),
                        (f"{head}/cls_score/kernel", 0),
                        (f"{head}/bbox_pred/kernel", 0)]))
    return out


def require_network() -> None:
    """End the run at once, non-zero, where the program under test does not
    know this network: ``make`` is the first of the configuration's
    functions a run (and its pre-compile child) calls, so nothing has been
    built yet."""
    from mx_rcnn_tpu.config import list_networks

    if NETWORK not in list_networks():
        raise SystemExit(
            f"r101-fpn-cascade-mask: this program has no network "
            f"{NETWORK!r} (mx_rcnn_tpu/config.py has {list_networks()}).  "
            f"No result.")


def make(net: dict, seed: int) -> dict:
    """{path: float32 array} for every leaf, drawn on the default device."""
    require_network()
    specs = stage_leaf_specs(net)
    sizes = [math.prod(shape) for _, shape, _ in specs]
    groups = channel_groups(net)

    @jax.jit
    def draw(base_key, key):
        normal = jax.random.normal(jax.random.fold_in(base_key, 1906),
                                   (sum(sizes),), jnp.float32)
        out, at = {}, 0
        for (path, shape, std), n in zip(specs, sizes):
            x = normal[at:at + n].reshape(shape)
            out[path] = x * jnp.asarray(std, jnp.float32)
            at += n
        for i, (width, members) in enumerate(groups):
            perm = jax.random.permutation(
                jax.random.fold_in(key, 2000 + i), width)
            for path, axis in members:
                out[path] = jnp.take(out[path], perm, axis=axis)
        return out

    flat = dict(mask_weights.make(net, seed))
    # stage 1's box output: r101-fpn's class-1 columns, at its own spread
    scale = jnp.asarray(box_gains(net, 1) / fpn_weights.GAINS[
        "rcnn_out/bbox_pred"], jnp.float32)
    flat["rcnn_out/bbox_pred/kernel"] = \
        flat["rcnn_out/bbox_pred/kernel"][:, 4:8] * scale
    flat["rcnn_out/bbox_pred/bias"] = flat["rcnn_out/bbox_pred/bias"][
        4:8] * jnp.asarray(0.1 / 0.02 * box_gains(net, 1), jnp.float32)
    flat.update(draw(seed_key(BASE_SEED), seed_key(seed)))
    return flat
