"""Compile-only gate for the Pallas kernels at production shapes.

The suite runs on the forced CPU mesh (tests/conftest.py), where the
public ``nms_pallas`` takes its pure-JAX oracle branch and
``assign_anchor`` its dense branch — a Mosaic regression would be
invisible to every other test.  The TPU compiler is installed here
without a chip, so these tests hand the INNER kernel functions to it for
a described (not attached) ``v5e:2x2`` topology: what the chip's compiler
would refuse — a mis-tiled slice, too much VMEM, an SMEM spec it cannot
lower — fails here, at no chip time.  Nothing executes; results are
checked on the chip by ``chip_smoke.py``'s kernels phase.

Rules this file keeps (``on-chip-measurement`` guide §2): the topology is
described inside a module-scoped fixture — never at import, in a
``skipif`` or in ``conftest.py`` — so every xdist worker collects the
same tests and only the worker that runs this file loads libtpu; the
compiles happen in the test's own process (a child could not load the
library its parent holds); all cases live in this ONE file; and the
persistent compile cache is off around them (an entry compiled for a
described chip cannot be read back without one).
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# mx_rcnn_tpu.kernels re-exports the FUNCTION under the module's name, so
# attribute access on the package yields the function — import the module
nms_mod = importlib.import_module("mx_rcnn_tpu.kernels.nms_pallas")
assign_mod = importlib.import_module("mx_rcnn_tpu.kernels.assign_pallas")

# the 608x1024 bucket every BASELINE.json config trains and serves at
H, W = 608, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this box
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _nms_shapes(n, batch=()):
    return (((*batch, n, 4), jnp.float32), ((*batch, n), jnp.float32),
            ((*batch, n), jnp.bool_))


def _fpn_joint_nms_shape(pre_nms: int) -> int:
    """Candidates ``propose_fpn`` hands its ONE joint NMS at the bucket:
    ``pre_nms`` split evenly over the levels, each level capped by its
    own anchor count (ops/proposal.py: k = min(k_level, n_level))."""
    from mx_rcnn_tpu.config import generate_config

    net = generate_config("resnet101_fpn", "PascalVOC").network
    strides = net.FPN_FEAT_STRIDES
    k_level = max(pre_nms // len(strides), 1)
    return sum(min(k_level, -(-H // s) * -(-W // s) * net.NUM_ANCHORS)
               for s in strides)


@pytest.mark.parametrize("n,max_out", [
    (12000, 2000),   # classic TRAIN contract (config.py RPN_PRE/POST_NMS)
    (6000, 300),     # classic TEST contract — the serve path
    (100, 300),      # n < max_out: the padded-output branch of _nms_post
])
def test_nms_core_compiles_for_v5e(one_chip, n, max_out):
    text = _compiled_text(
        lambda b, s, v: nms_mod._nms_core(b, s, v, max_out, 0.7),
        one_chip, *_nms_shapes(n))
    assert "tpu_custom_call" in text


def test_nms_batched_vmap_rule_compiles_for_v5e(one_chip):
    # the detector vmaps propose over images; Mosaic cannot auto-batch the
    # SMEM specs, so the custom_vmap rule must lower to lax.map over
    # single-image kernel calls
    fn = jax.vmap(nms_mod._nms_vmappable(300, 0.7))
    text = _compiled_text(fn, one_chip, *_nms_shapes(6000, batch=(2,)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("phase", ["TRAIN", "TEST"])
def test_fpn_joint_nms_compiles_for_v5e(one_chip, phase):
    from mx_rcnn_tpu.config import generate_config

    c = getattr(generate_config("resnet101_fpn", "PascalVOC"), phase)
    n = _fpn_joint_nms_shape(c.RPN_PRE_NMS_TOP_N)
    max_out = c.RPN_POST_NMS_TOP_N
    text = _compiled_text(
        lambda b, s, v: nms_mod._nms_core(b, s, v, max_out,
                                          c.RPN_NMS_THRESH),
        one_chip, *_nms_shapes(n))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [4, 6])
def test_nms_batched_rule_compiles_for_four_chips(topo, batch):
    # the data-parallel step: XLA cannot partition a Mosaic kernel and jax
    # refuses to lower one for four devices outside a shard_map, so under
    # MeshPlan.traced the batching rule must shard_map itself — over the
    # batch rows when the data axis divides them (4), whole on every
    # device when it does not (6)
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mx_rcnn_tpu.parallel import make_mesh

    plan = make_mesh(list(topo.devices), data=4)
    rows = NamedSharding(plan.mesh, P("data") if batch % 4 == 0 else P())
    args = [jax.ShapeDtypeStruct(s, d, sharding=rows)
            for s, d in _nms_shapes(12000, batch=(batch,))]
    fn = plan.traced(jax.vmap(nms_mod._nms_vmappable(2000, 0.7)))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_assign_reduce_compiles_for_v5e(one_chip):
    # FPN P2 at the bucket: 152 x 256 cells x 3 anchors, MAX_GT = 100 —
    # guarded for as long as tpu.ASSIGN_FUSED stays in the tree
    n, g = (H // 4) * (W // 4) * 3, 100
    assert n == 116736
    text = _compiled_text(
        assign_mod.assign_reduce_pallas, one_chip,
        ((n, 4), jnp.float32), ((g, 4), jnp.float32), ((g,), jnp.bool_),
        ((n,), jnp.bool_))
    assert "tpu_custom_call" in text


# ---- the 800 x 1344 bucket of benchmark cell fpn-serve-closed (r101-fpn,
# TEST.RPN_PRE_NMS_TOP_N=5000 -> 1000 a level, RPN_POST_NMS_TOP_N=1000) ----

SERVE_H, SERVE_W = 800, 1344


def _serve_level_anchors() -> list:
    """Anchors a level at the served bucket: 3 a cell on strides 4..32, P6
    the stride-2 subsample of P5's 25 x 42 map."""
    sizes = [(SERVE_H // s, SERVE_W // s) for s in (4, 8, 16, 32)]
    sizes.append(((sizes[-1][0] + 1) // 2, (sizes[-1][1] + 1) // 2))
    return [h * w * 3 for h, w in sizes]


def test_served_fpn_joint_nms_compiles_for_v5e(one_chip):
    # what propose_fpn's ONE joint NMS meets there: 4 x 1000 + P6's 819
    n = sum(min(1000, a) for a in _serve_level_anchors())
    assert n == 4819
    text = _compiled_text(
        lambda b, s, v: nms_mod._nms_core(b, s, v, 1000, 0.7),
        one_chip, *_nms_shapes(n))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("level", range(5), ids=["P2", "P3", "P4", "P5", "P6"])
def test_level_topk_compiles_at_the_served_row_lengths(one_chip, level):
    # the windowed-TopK fence of ops/proposal.py::_level_topk at the rows it
    # now meets: (16, 12600), (16, 3150), (8, 1575), (2, 1575) at k = 1000
    # and their (g * k,) second stages; P6's 819 anchors are fewer than k
    # and take the argsort branch
    from mx_rcnn_tpu.ops.proposal import _level_topk

    n = _serve_level_anchors()[level]
    assert n == (201600, 50400, 12600, 3150, 819)[level]
    k = min(1000, n)
    text = _compiled_text(lambda s: _level_topk(s, k), one_chip,
                          ((n,), jnp.float32))
    assert ("/top_k" in text) == (level < 4)


# ---- the blocked attention kernel of the ViT trunk's global blocks at the
# shapes of benchmark cell vitdet-serve-closed: 8 images x 12 heads, a
# 64 x 64 grid of tokens, head dimension 64 (PR 34) ----

attn_mod = importlib.import_module("mx_rcnn_tpu.kernels.attention_pallas")


@pytest.mark.parametrize("heads,grid", [(96, 64), (24, 32)])
def test_blocked_attention_compiles_for_v5e(one_chip, heads, grid):
    """At the served shape and at a 512 px bucket's: Mosaic takes the
    tiling (whole key blocks of 512, the 0/1 rows stacked under k^T in a
    once-a-head scratch), the call carries the name the benchmark's
    ``names.attn_kernel`` reads, and no score matrix is ever a buffer: the
    program's temporaries stay near the folded q rows and k^T (0.4 GB at
    96 heads), three orders under the 6.4 GB of 96 x 4096 x 4096 floats."""
    n, d = grid * grid, 64
    shapes = [((heads, n, d), jnp.bfloat16)] * 3 + [
        ((heads, n, 2 * grid), jnp.bfloat16)]
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in shapes]
    compiled = jax.jit(lambda q, k, v, rel: attn_mod.attention_blocked(
        q, k, v, rel, grid, d ** -0.5)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"%{attn_mod.KERNEL_NAME}" in text
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 3 * heads * n * (d + 2 * grid) * 2
