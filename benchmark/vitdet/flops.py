"""``vitdet-b-mask``'s operations and bytes, counted from shapes alone: the
``flops`` row of ``benchmark/README.md``, "A configuration".  From the plain
reference's list of layers (``reference.mrcnn_vitdet.layers``) and the
configuration's sizes, never from the program under test or the compiler's
cost analysis.

A multiply-accumulate is two operations.  Counted, as the reference
computes them: the patch embedding; every block's qkv and output projection
(on the windowed blocks' **padded** grid, 70 x 70 at the published sizes:
that is the published model's work — a kernel's own block padding would not
be), its two MLP matmuls, and its attention — scores, the two relative
terms' products with q, the weighted sum — inside each window or over the
whole grid; the simple feature pyramid's deconvs and convs; the RPN head on
each of the five levels; the box head once a RoI.  The mask head is
``mask_flops_per_image``'s, a RoI masked.  **Not counted**, whatever
implements it: LayerNorm, GELU, softmax's exponentials, RoIAlign's
interpolation, ReLU, max-pool, box decoding, NMS.
"""

from __future__ import annotations

from benchmark.flops import nms_work, roofline_seconds  # noqa: F401
from benchmark.reference.mrcnn_vitdet import layers, sizes


def layer_macs(layer) -> int:
    (_path, kind, kh, kw, cin, cout, _bias, _part, positions) = layer
    return positions * cin * cout * (kh * kw if kind == "conv" else 1)


def attention_macs(net: dict) -> dict:
    """{"block_window", "block_global"}: one block's attention, an image:
    for every head and every grid of S x S tokens, S^2 x S^2 x d twice (the
    scores, the weighted sum) and S^2 x S x d twice (the two relative
    terms)."""
    v, sz = net["vit"], sizes(net)
    d = v["width"] // v["heads"]

    def one(s, grids):
        n = s * s
        return grids * v["heads"] * (2 * n * n * d + 2 * n * s * d)

    return {"block_window": one(v["window"], sz["windows"]),
            "block_global": one(sz["grid"], 1)}


def predict_flops_per_image(net: dict) -> dict:
    """{"patch_embed", "block_window", "block_global", "attention", "sfp",
    "rpn", "head", "total"}: FLOPs of one image's inference at the bucket's
    size with the test-time number of RoIs.  ``attention`` is the part of
    the two kinds of block that is attention proper (in their sums
    already, not in the total twice)."""
    v = net["vit"]
    macs = {"patch_embed": 0, "block_window": 0, "block_global": 0,
            "sfp": 0, "rpn": 0, "box_head": 0}
    for layer in layers(net):
        if layer[7] in macs:
            macs[layer[7]] += layer_macs(layer)
    attn = attention_macs(net)
    n_glob = len(v["global_blocks"])
    per_kind = {"block_window": v["depth"] - n_glob, "block_global": n_glob}
    for kind, blocks in per_kind.items():
        macs[kind] += blocks * attn[kind]
    macs["head"] = macs.pop("box_head") * net["test_post_nms"]
    out = {k: 2 * m for k, m in macs.items()}
    out["total"] = sum(out.values())
    out["attention"] = 2 * sum(per_kind[k] * attn[k] for k in per_kind)
    return out


def attn_work(net: dict) -> dict:
    """The operations and bytes of one image's global blocks' attention
    proper, defined by the mathematics whatever implements it: for every
    head ``softmax(s q k^T + B) v`` over N = G^2 tokens of width d — N x N x
    d multiply-accumulates twice — with q, k, v and the output moved once
    in bfloat16.  The relative terms' small products with q (1.5 % more)
    are ``predict_flops_per_image``'s, not credited here: a kernel that is
    handed them ready is not paid for them."""
    v, sz = net["vit"], sizes(net)
    d = v["width"] // v["heads"]
    n = sz["grid"] ** 2
    heads = len(v["global_blocks"]) * v["heads"]
    return {"ops": heads * 2 * (2 * n * n * d),
            "bytes": heads * 4 * n * d * 2}


def mask_flops_per_image(net: dict, rois: float) -> float:
    """FLOPs the mask head requires for ``rois`` RoIs of one image."""
    return 2.0 * rois * sum(layer_macs(la) for la in layers(net)
                            if la[7] == "mask_head")
