"""``vitdet-b-mask``'s weights from the seed: the ``weights`` row of
``benchmark/README.md``, "A configuration" (``make(net, seed)``; ``as_tree``
and ``check_against`` stay in ``benchmark/weights.py`` for every
configuration).

As ``benchmark/weights.py`` argues: every seed gives the **same network in
another order**.  The values are drawn once from ``BASE_SEED`` and the seed
permutes hidden channels — the trunk's residual width in every leaf that
reads or writes it (LayerNorm does not care for the order of its channels),
every MLP's hidden width, the pyramid's widths, the RPN's and the heads'
hidden layers — so the arrays differ from seed to seed and the function
does not, up to the order of summation.  The images still come from the
seed.

One jitted call draws every leaf on the device in float32.  The list of
leaves is the plain reference's own (``reference.mrcnn_vitdet.layers``,
``norms``, ``position_leaves``), never the program's: the driver refuses to
run unless the program's tree has exactly these names and shapes.

Scales: kernels at ``gain / sqrt(fan_in)`` (1 after a LayerNorm, 2 ** 0.5
after a ReLU or GELU), so that q, k and the MLP's hidden units have a spread
near 1 and a head's scores of about a unit; position vectors at 0.5 and
relative tables at 0.1 (a bias of about 0.8 a score), so that a token's
place decides what it attends to and a fault in either term shows;
LayerNorm scales in 0.8..1.2.  Every level of the simple feature pyramid
ends in a LayerNorm, and that LayerNorm's scale and bias carry a gain a
level (``LEVEL_GAINS``), as ``benchmark/fpn/weights.py``'s smoothing convs
do, so that proposals of every size survive the joint NMS and all of
P2..P5 pool.  Output gains as the other two pyramid configurations' (class
logits spread by a few units, box deltas of about a tenth); the mask head's
kernels each sum to zero over their input channels and its output gain is
6, as ``benchmark/mask/weights.py`` found necessary for masks that are
neither empty nor full.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import mrcnn_vitdet as ref
from benchmark.weights import seed_key

BASE_SEED = 5
NETWORK = "vitdet_b_mask"
R2 = 2.0 ** 0.5
# gain of a kernel's draw where it is not 1 (by the path's last part)
GAINS = {"fc1": 1.0, "fc2": R2, "p2_deconv2": R2, "rpn_conv_3x3_2": R2,
         "fc6": R2, "mask_deconv": R2, "rpn_cls_score": 1.5,
         "rpn_bbox_pred": 0.1, "cls_score": 3.2, "bbox_pred": 0.07,
         "mask_out": 6.0}
POS_STD, REL_STD = 0.5, 0.1
# gain of a level's last LayerNorm (scale and bias): what the shared RPN
# head's objectness rides on.  Every level leaves its LayerNorm at the same
# spread, and then the joint NMS keeps the small boxes of P2's and P3's
# anchors alone: 99.97 % of RoIs pooled on P2 (my chip run, PR 34, call 2).
# A larger gain widens a level's objectness but also deepens the negative
# mean this base network's head has on P5 and P6 (-1.35 at gain 1, -4.1 at
# 3); read **negated** the same maps give it a mean of +0.2, so the two
# upper levels' gains are negative (a LayerNorm scale may have either
# sign).  With these the reference's 1000 RoIs of a 400 x 500 body pool
# 264 / 474 / 234 / 28 on P2 / P3 / P4 / P5 (my chip run, PR 34, call 7;
# the scan is in PERF.md section 4)
LEVEL_GAINS = {2: 0.7, 3: 0.7, 4: -1.6, 5: -3.0}


def leaf_specs(net: dict):
    """[(path, shape, kind)] of every parameter, in a fixed order."""
    out = []
    for (path, kind, kh, kw, cin, cout, bias, _part, _n) in ref.layers(net):
        shape = (cin, cout) if kind == "fc" else (kh, kw, cin, cout)
        fan_in = cin * kh * kw if kind == "conv" else cin
        std = GAINS.get(path.rsplit("/", 1)[1], 1.0) / fan_in ** 0.5
        centred = path.startswith("mask_head/")
        out.append((f"{path}/kernel", shape,
                    ("centred" if centred else "normal", std)))
        if bias:
            out.append((f"{path}/bias", (cout,), ("normal", 0.02)))
    for path, width in ref.norms(net):
        g = (LEVEL_GAINS.get(int(path[len("neck/post")]), 1.0)
             if path.startswith("neck/post") else 1.0)
        out.append((f"{path}/scale", (width,), ("uniform", 0.8 * g, 1.2 * g)))
        out.append((f"{path}/bias", (width,), ("normal", 0.05 * g)))
    for path, shape in ref.position_leaves(net):
        out.append((path, shape, ("normal", POS_STD if path.endswith(
            "pos_embed") else REL_STD)))
    return out


def channel_groups(net: dict):
    """[(width, [(leaf path, axis), ...])]: each group of hidden channels
    that may be permuted together without changing the function."""
    v = net["vit"]
    c, ch = v["width"], net["fpn_channels"]
    norm = lambda p: [(f"{p}/scale", 0), (f"{p}/bias", 0)]  # noqa: E731
    out_of = lambda p: [(f"{p}/kernel", -1), (f"{p}/bias", 0)]  # noqa: E731
    stream = [("backbone/patch_embed/kernel", 3),
              ("backbone/patch_embed/bias", 0), ("backbone/pos_embed", 3),
              ("neck/p2_deconv1/kernel", 2), ("neck/p3_deconv/kernel", 2),
              ("neck/lateral4/kernel", 2), ("neck/lateral5/kernel", 2)]
    groups = []
    for i in range(v["depth"]):
        b = f"backbone/block{i}"
        stream += (norm(f"{b}/norm1") + norm(f"{b}/norm2")
                   + [(f"{b}/attn/qkv/kernel", 0), (f"{b}/fc1/kernel", 0)]
                   + out_of(f"{b}/attn/proj") + out_of(f"{b}/fc2"))
        groups.append((v["mlp_ratio"] * c,
                       out_of(f"{b}/fc1") + [(f"{b}/fc2/kernel", 0)]))
    groups.append((c, stream))
    groups.append((c // 2, out_of("neck/p2_deconv1") + norm("neck/p2_norm")
                   + [("neck/p2_deconv2/kernel", 2)]))
    groups.append((c // 4, out_of("neck/p2_deconv2")
                   + [("neck/lateral2/kernel", 2)]))
    groups.append((c // 2, out_of("neck/p3_deconv")
                   + [("neck/lateral3/kernel", 2)]))
    levels = (2, 3, 4, 5)
    for lvl in levels:
        groups.append((ch, [(f"neck/lateral{lvl}/kernel", 3)]
                       + norm(f"neck/lateral{lvl}_norm")
                       + [(f"neck/post{lvl}/kernel", 2)]))
    groups.append((ch, [leaf for lvl in levels for leaf in
                        [(f"neck/post{lvl}/kernel", 3)]
                        + norm(f"neck/post{lvl}_norm")]
                   + [("rpn/rpn_conv_3x3/kernel", 2),
                      ("head_body/conv1/kernel", 2),
                      ("mask_head/mask_conv1/kernel", 2)]))
    rpn = [ref.rpn_conv_name(i) for i in range(1, net["rpn_convs"] + 1)]
    for a, nxt in zip(rpn, rpn[1:] + [None]):
        groups.append((ch, out_of(a) + (
            [(f"{nxt}/kernel", 2)] if nxt else
            [("rpn/rpn_cls_score/kernel", 2),
             ("rpn/rpn_bbox_pred/kernel", 2)])))
    for i in range(1, net["head_convs"]):      # the last feeds the flatten
        groups.append((ch, [(f"head_body/conv{i}/kernel", 3)]
                       + norm(f"head_body/conv{i}_norm")
                       + [(f"head_body/conv{i + 1}/kernel", 2)]))
    groups.append((net["head_hidden"], out_of("head_body/fc6")
                   + [("rcnn_out/cls_score/kernel", 0),
                      ("rcnn_out/bbox_pred/kernel", 0)]))
    mc = net["mask_channels"]
    for i in range(1, net["mask_convs"] + 1):
        nxt = (f"mask_head/mask_conv{i + 1}" if i < net["mask_convs"]
               else "mask_head/mask_deconv")
        groups.append((mc, [(f"mask_head/mask_conv{i}/kernel", 3)]
                       + norm(f"mask_head/mask_conv{i}_norm")
                       + [(f"{nxt}/kernel", 2)]))
    groups.append((mc, out_of("mask_head/mask_deconv")
                   + [("mask_head/mask_out/kernel", 2)]))
    return groups


def require_network() -> None:
    """End the run at once, non-zero, where the program under test does not
    know this network: ``make`` is the first of the configuration's
    functions a run (and its pre-compile child) calls, so nothing has been
    built yet."""
    from mx_rcnn_tpu.config import list_networks

    if NETWORK not in list_networks():
        raise SystemExit(
            f"vitdet-b-mask: this program has no network {NETWORK!r} "
            f"(mx_rcnn_tpu/config.py has {list_networks()}).  No result.")


def make(net: dict, seed: int) -> dict:
    """{path: float32 array} for every leaf, drawn on the default device."""
    require_network()
    specs = leaf_specs(net)
    sizes = [math.prod(shape) for _, shape, _ in specs]
    groups = channel_groups(net)

    @jax.jit
    def draw(base_key, key):
        kn, ku = jax.random.split(jax.random.fold_in(base_key, 2203))
        normal = jax.random.normal(kn, (sum(sizes),), jnp.float32)
        out, at, u = {}, 0, 0
        for (path, shape, kind), n in zip(specs, sizes):
            if kind[0] == "uniform":
                x = kind[1] + (kind[2] - kind[1]) * jax.random.uniform(
                    jax.random.fold_in(ku, u), (n,), jnp.float32)
                u += 1
            else:
                x = kind[1] * normal[at:at + n]
            x = x.reshape(shape)
            if kind[0] == "centred":     # zero sum over the input channels
                x = x - x.mean(axis=-2, keepdims=True)
            out[path] = x
            at += n
        for i, (width, members) in enumerate(groups):
            perm = jax.random.permutation(jax.random.fold_in(key, i), width)
            for path, axis in members:
                out[path] = jnp.take(out[path], perm,
                                     axis=axis % out[path].ndim)
        return out

    return draw(seed_key(BASE_SEED), seed_key(seed))
