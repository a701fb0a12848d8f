"""``r101-fpn-mask``'s weights from the seed: the ``weights`` row of
``benchmark/README.md``, "A configuration".

``benchmark.fpn.weights.make`` as it is — the trunk, the neck, the RPN and
the box head of ``r101-fpn``, the same base network for every seed with its
hidden channels in a seeded order — plus the mask head's six layers
(``reference.mrcnn_fpn.mask_layers``: ``mask_head/mask_conv1..4``,
``mask_deconv``, ``mask_out``), drawn the same way: values once from
``BASE_SEED``, the seed permuting each hidden width (a conv's output
channels with its bias and the next layer's input channels), in one jitted
call on the device, float32.

Scales: He-normal kernels through the four convs and the deconv (each
followed by a ReLU, so the crop's spread of about 1 is kept), and an output
gain that gives the per-class logits a spread of two to three units inside
one mask, so most of its pixels lie well away from the cut at 0.5 and a
comparison of masks is not a comparison of coin flips.  **Every kernel sums
to zero over its input channels** (a tap and an output channel at a time):
six ReLU layers of plain He-normal kernels pile the activations' common
positive mean into a constant of about +5 on the logits of the few classes
the records carry, and every mask comes out full — on the chip
``mask_fill`` read 0.94 and ``mask_gap`` 0 for the program and its float8
control alike (PR 32, first call), a comparison the weights decide.
Centred, a layer answers only to what differs between channels; at
ResNet-101 widths the logits' mean inside a mask falls under 1 and every
record's fill lies between 0.1 and 0.9 (the comparison prints
``mask_fill``, the share of set pixels inside the paste window, and holds
it to a range).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.fpn import weights as fpn_weights
from benchmark.reference import mrcnn_fpn
from benchmark.weights import seed_key

BASE_SEED = fpn_weights.BASE_SEED
GAINS = {"mask_head/mask_out": 6.0}


def layers_of(net: dict):
    return mrcnn_fpn.mask_layers(net["num_classes"], net["mask_channels"],
                                 net["mask_convs"])


def mask_leaf_specs(net: dict):
    """[(path, shape, std)] of the mask head's parameters, in a fixed
    order."""
    out = []
    for (path, kind, kh, kw, cin, cout, _side) in layers_of(net):
        # a stride-2 2x2 deconv's output cell hears one tap: fan-in cin
        fan_in = cin if kind == "deconv" else cin * kh * kw
        std = GAINS.get(path, 2.0 ** 0.5) / fan_in ** 0.5
        out.append((f"{path}/kernel", (kh, kw, cin, cout), std))
        out.append((f"{path}/bias", (cout,), 0.02))
    return out


def leaf_specs(net: dict):
    """[(path, shape)] of every parameter of the configuration: the pyramid
    detector's and the mask head's."""
    return ([(p, s) for p, s, _ in fpn_weights.leaf_specs(net)]
            + [(p, s) for p, s, _ in mask_leaf_specs(net)])


def channel_groups(net: dict):
    """[(width, [(leaf path, axis), ...])]: each hidden width of the mask
    head: a layer's output channels, its bias, the next layer's inputs."""
    paths = [layer[0] for layer in layers_of(net)]
    return [(net["mask_channels"],
             [(f"{a}/kernel", 3), (f"{a}/bias", 0), (f"{b}/kernel", 2)])
            for a, b in zip(paths, paths[1:])]


def require_mask_serving() -> None:
    """End the run at once, non-zero, where the program under test cannot
    answer masks: a tree from before the engine's mask stage builds this
    network and serves its boxes alone, at the pyramid detector's rate —
    a window of that is no reading of this cell.  ``make`` is the first of
    the configuration's functions a run (and its pre-compile child) calls,
    so nothing has been built yet."""
    from mx_rcnn_tpu.serve.engine import ServeEngine

    if not hasattr(ServeEngine, "_mask_stage"):
        raise SystemExit(
            "r101-fpn-mask: this program's serve engine has no mask stage "
            "(serve/engine.py ServeEngine._mask_stage); it would answer "
            "boxes without masks.  No result.")


def make(net: dict, seed: int) -> dict:
    """{path: float32 array} for every leaf, drawn on the default device."""
    require_mask_serving()
    specs = mask_leaf_specs(net)
    sizes = [math.prod(shape) for _, shape, _ in specs]
    groups = channel_groups(net)

    @jax.jit
    def draw(base_key, key):
        normal = jax.random.normal(jax.random.fold_in(base_key, 1703),
                                   (sum(sizes),), jnp.float32)
        out, at = {}, 0
        for (path, shape, std), n in zip(specs, sizes):
            x = (std * normal[at:at + n]).reshape(shape)
            if len(shape) == 4:     # a kernel: zero sum over its inputs
                x = x - x.mean(axis=2, keepdims=True)
            out[path] = x
            at += n
        for i, (width, members) in enumerate(groups):
            perm = jax.random.permutation(
                jax.random.fold_in(key, 1000 + i), width)
            for path, axis in members:
                out[path] = jnp.take(out[path], perm, axis=axis)
        return out

    flat = dict(fpn_weights.make(net, seed))
    flat.update(draw(seed_key(BASE_SEED), seed_key(seed)))
    return flat
