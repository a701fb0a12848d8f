"""Weights from the seed, made by the benchmark and by nobody else.

Every seed gives the **same network in another order**: the values are drawn
once from ``BASE_SEED`` and the seed permutes the hidden channels of every
bottleneck and of the RPN (a producer's output channels with its BN, its
consumers' input channels).  The arrays differ from seed to seed, the
function does not, up to the order of summation.  Why: the host's work after
the forward depends on the scores (how many of the 300 x 80 candidates pass
the threshold, how many survive the per-class NMS); with weights drawn anew
per seed those counts moved by a third and the saturated rate by 7 % from
seed to seed while two runs of one seed agreed to four digits (PERF.md
section 6).  The images still come from the seed.

One jitted call draws every leaf on the device, in float32 (the type the
program serves them in).  The list of leaves comes from the plain
reference's own description of the architecture
(``reference.frcnn_c4.conv_layers``), never from the program: the driver
checks that the program's parameter tree has exactly these names and shapes
and refuses to run otherwise.

The draws are scaled so that activations stay O(1) through 100 layers with
frozen BN (He-normal kernels; the last BN of each bottleneck at a gain of
about 0.3, so the residual stream grows slowly) and so that the RPN's and the
head's outputs are decisive (objectness logits and class logits with a spread
of a few units, box deltas of about a tenth): a detector whose scores are all
1/K would make every comparison of detections a comparison of ties.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import frcnn_c4

# output gains of the four prediction layers: std of the draw is
# gain / sqrt(fan_in); set on the CPU so that the outputs have the spreads
# named above for inputs of the scale the trunk produces
BASE_SEED = 5    # the seed of the rate sweep that fixed the open cell's rate

GAINS = {"rpn/rpn_cls_score": 0.8, "rpn/rpn_bbox_pred": 0.055,
         "rcnn_out/cls_score": 0.75, "rcnn_out/bbox_pred": 0.036}


def leaf_specs(net: dict):
    """[(path, shape, kind)] of every parameter, in a fixed order."""
    out = []
    for (path, kh, kw, cin, cout, _s, bn, bias, _part) in \
            frcnn_c4.conv_layers(net["depth"], net["num_classes"],
                                 net["num_anchors"]):
        shape = (cin, cout) if kh == 0 else (kh, kw, cin, cout)
        fan_in = cin * max(kh, 1) * max(kw, 1)
        gain = GAINS.get(path)
        std = (gain if gain is not None else 2.0 ** 0.5) / fan_in ** 0.5
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


def seed_key(seed: int):
    """A key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


def channel_groups(net: dict):
    """[(width, [(leaf path, axis), ...])]: each group of hidden channels
    that may be permuted together without changing the function."""
    bn = ("gamma", "beta", "mean", "var")
    groups = []
    for (path, kh, _kw, _cin, cout, _s, bnp, _b, part) in \
            frcnn_c4.conv_layers(net["depth"], net["num_classes"],
                                 net["num_anchors"]):
        unit, _, name = path.rpartition("/")
        if name in ("conv1", "conv2") and "/unit" in path:
            nxt = f"{unit}/conv{int(name[-1]) + 1}/kernel"
            groups.append((cout, [(f"{path}/kernel", 3), (nxt, 2)]
                           + [(f"{bnp}/{k}", 0) for k in bn]))
        elif name == "rpn_conv_3x3":
            groups.append((cout, [(f"{path}/kernel", 3), (f"{path}/bias", 0),
                                  ("rpn/rpn_cls_score/kernel", 2),
                                  ("rpn/rpn_bbox_pred/kernel", 2)]))
    return groups


def make(net: dict, seed: int) -> dict:
    """{path: float32 array} for every leaf, drawn on the default device."""
    specs = leaf_specs(net)
    sizes = [math.prod(shape) for _, shape, _ in specs]
    groups = channel_groups(net)

    @jax.jit
    def draw(base_key, key):
        # two flat draws, cut into the leaves: one program of two random
        # calls compiles in a second where one call a leaf takes a minute
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


def as_tree(flat: dict) -> dict:
    """{"a/b/c": x} -> {"a": {"b": {"c": x}}}, the shape a flax tree has."""
    tree: dict = {}
    for path, x in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for k in parents:
            node = node.setdefault(k, {})
        node[leaf] = x
    return tree


def check_against(flat: dict, program_shapes) -> None:
    """Raise unless the program's tree (a pytree of things with ``.shape``)
    has exactly the benchmark's leaves."""
    theirs = {"/".join(str(getattr(k, "key", k)) for k in path): tuple(s.shape)
              for path, s in jax.tree_util.tree_flatten_with_path(
                  program_shapes)[0]}
    ours = {k: tuple(v.shape) for k, v in flat.items()}
    if theirs != ours:
        diff = sorted(set(theirs.items()) ^ set(ours.items()))[:8]
        raise RuntimeError(f"the program's parameters are not the "
                           f"reference's: {diff}")
