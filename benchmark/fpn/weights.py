"""``r101-fpn``'s weights from the seed: the ``weights`` row of
``benchmark/README.md``, "A configuration" (``make(net, seed)``; ``as_tree``
and ``check_against`` stay in ``benchmark/weights.py`` for every
configuration).

As ``benchmark/weights.py`` argues for ``r101-c4``: every seed gives the
**same network in another order**.  The values are drawn once from
``BASE_SEED`` and the seed permutes hidden channels (a producer's output
channels with its BN or bias, its consumers' input channels), so the arrays
differ from seed to seed and the function does not, up to the order of
summation — the host's work after the forward (how many of the 1000 x 80
candidates pass the threshold and survive the per-class NMS) does not move
with the seed.  The images still come from the seed.

One jitted call draws every leaf on the device in float32.  The list of
leaves is the plain reference's own (``reference.frcnn_fpn.conv_layers``),
never the program's: the driver refuses to run unless the program's tree has
exactly these names and shapes.

Scales: He-normal kernels, the last BN of a bottleneck at a gain of about
0.3 (``benchmark/weights.py``'s trunk); the neck's laterals and smoothing
convs at gains that keep P2..P5 at a spread near 1 although no ReLU follows
them and each level adds the one above; and output gains that make the RPN's
and the head's outputs decisive on all five levels (objectness and class
logits with a spread of a few units, box deltas of about a tenth): a
detector whose scores are all 1/K would make every comparison of detections
a comparison of ties.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import frcnn_fpn
from benchmark.weights import seed_key

BASE_SEED = 5    # as benchmark/weights.py: one base network for every seed

# std of a kernel's draw is gain / sqrt(fan_in); 2 ** 0.5 (He) where not
# named.  Set on the CPU at ResNet-101 so that the outputs have the spreads
# the docstring names (tests/benchmark_checks/test_fpn_reference.py holds them)
GAINS = {"neck/lateral2": 0.4, "neck/lateral3": 0.4, "neck/lateral4": 0.4,
         "neck/lateral5": 0.4,
         # a level: its objectness spread rides on its map's scale, and the
         # shared RPN head meets a different DC on every level; with these
         # the joint NMS keeps boxes of every size and all of P2..P5 pool
         "neck/post2": 1.0, "neck/post3": 0.5, "neck/post4": 2.0,
         "neck/post5": 1.6,
         "rpn/rpn_cls_score": 1.5, "rpn/rpn_bbox_pred": 0.1,
         "rcnn_out/cls_score": 3.2, "rcnn_out/bbox_pred": 0.07}


def layers_of(net: dict):
    return frcnn_fpn.conv_layers(net["depth"], net["num_classes"],
                                 net["num_anchors"], net["fpn_channels"],
                                 net["head_hidden"])


def leaf_specs(net: dict):
    """[(path, shape, kind)] of every parameter, in a fixed order."""
    out = []
    for (path, kh, kw, cin, cout, _s, bn, bias, _part) in layers_of(net):
        shape = (cin, cout) if kh == 0 else (kh, kw, cin, cout)
        fan_in = cin * max(kh, 1) * max(kw, 1)
        std = GAINS.get(path, 2.0 ** 0.5) / fan_in ** 0.5
        out.append((f"{path}/kernel", shape, ("normal", std)))
        if bias:
            out.append((f"{path}/bias", (cout,), ("normal", 0.02)))
        if bn:
            last = bn.endswith("bn3")
            out.append((f"{bn}/gamma", (cout,),
                        ("uniform", 0.1, 0.3) if last else ("uniform", 0.8, 1.2)))
            out.append((f"{bn}/beta", (cout,), ("normal", 0.05)))
            out.append((f"{bn}/mean", (cout,), ("normal", 0.05)))
            out.append((f"{bn}/var", (cout,), ("uniform", 0.8, 1.2)))
    return out


def channel_groups(net: dict):
    """[(width, [(leaf path, axis), ...])]: each group of hidden channels
    that may be permuted together without changing the function: the two
    inner widths of every bottleneck, the neck's merged maps (all four
    laterals out, all four smoothing convs in), the RPN's hidden conv, and
    the head's two hidden layers."""
    bn = ("gamma", "beta", "mean", "var")
    groups = []
    for (path, _kh, _kw, _cin, cout, _s, bnp, _b, _part) in layers_of(net):
        unit, _, name = path.rpartition("/")
        if name in ("conv1", "conv2") and "/unit" in path:
            nxt = f"{unit}/conv{int(name[-1]) + 1}/kernel"
            groups.append((cout, [(f"{path}/kernel", 3), (nxt, 2)]
                           + [(f"{bnp}/{k}", 0) for k in bn]))
    c = net["fpn_channels"]
    groups.append((c, [(f"neck/lateral{i}/{leaf}", ax) for i in (2, 3, 4, 5)
                       for leaf, ax in (("kernel", 3), ("bias", 0))]
                   + [(f"neck/post{i}/kernel", 2) for i in (2, 3, 4, 5)]))
    groups.append((c, [("rpn/rpn_conv_3x3/kernel", 3),
                       ("rpn/rpn_conv_3x3/bias", 0),
                       ("rpn/rpn_cls_score/kernel", 2),
                       ("rpn/rpn_bbox_pred/kernel", 2)]))
    h = net["head_hidden"]
    groups.append((h, [("head_body/fc6/kernel", 1), ("head_body/fc6/bias", 0),
                       ("head_body/fc7/kernel", 0)]))
    groups.append((h, [("head_body/fc7/kernel", 1), ("head_body/fc7/bias", 0),
                       ("rcnn_out/cls_score/kernel", 0),
                       ("rcnn_out/bbox_pred/kernel", 0)]))
    return groups


def make(net: dict, seed: int) -> dict:
    """{path: float32 array} for every leaf, drawn on the default device."""
    specs = leaf_specs(net)
    sizes = [math.prod(shape) for _, shape, _ in specs]
    groups = channel_groups(net)

    @jax.jit
    def draw(base_key, key):
        # two flat draws cut into the leaves (benchmark/weights.py: one
        # program of two random calls compiles in a second)
        kn, ku = jax.random.split(base_key)
        normal = jax.random.normal(kn, (sum(sizes),), jnp.float32)
        unif = jax.random.uniform(ku, (sum(sizes),), jnp.float32)
        out, at = {}, 0
        for (path, shape, kind), n in zip(specs, sizes):
            if kind[0] == "normal":
                x = kind[1] * normal[at:at + n]
            else:
                x = kind[1] + (kind[2] - kind[1]) * unif[at:at + n]
            out[path] = x.reshape(shape)
            at += n
        for i, (width, members) in enumerate(groups):
            perm = jax.random.permutation(jax.random.fold_in(key, i), width)
            for path, axis in members:
                out[path] = jnp.take(out[path], perm, axis=axis)
        return out

    return draw(seed_key(BASE_SEED), seed_key(seed))
