#!/usr/bin/env python
"""Per-component backbone timing on the real chip.

Times fwd and fwd+bwd of the ResNet-101 conv body and its pieces at the
bench shape (1, 608, 1024, 3) to locate where the conv-bound ~19 ms goes
(ROADMAP: conv ceiling investigation).  Chained-steps timing with a
scalar readback fence (fetching whole activations would dominate).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import flax.linen as nn
from mx_rcnn_tpu.models.backbones import ResNetConv, ResNetStage

assert jax.default_backend() == "tpu"

H, W = 608, 1024
REPEAT = 20


def timeit(fn, *args):
    # warm up with a full chain: the first chain after compile pays
    # one-time costs and single blocked calls pay dispatch latency; only
    # the second-or-later chained run measures device time
    best = None
    for _ in range(3):
        t0 = time.time()
        for _ in range(REPEAT):
            out = fn(*args)
        _ = float(jax.device_get(out))  # scalar fence
        dt = (time.time() - t0) / REPEAT * 1000
        best = dt if best is None else min(best, dt)
    return best


def bench_module(name, mod, x):
    params = mod.init(jax.random.PRNGKey(0), x)

    def loss(p, x):
        out = mod.apply(p, x)
        leaves = jax.tree_util.tree_leaves(out)
        return sum(jnp.sum(l.astype(jnp.float32) ** 2) for l in leaves)

    fwd = jax.jit(loss)

    @jax.jit
    def fwdbwd(p, x):
        l, g = jax.value_and_grad(loss)(p, x)
        return l + sum(jnp.sum(jnp.abs(t.astype(jnp.float32)))
                       for t in jax.tree_util.tree_leaves(g)) * 0.0

    tf = timeit(fwd, params, x)
    tb = timeit(fwdbwd, params, x)
    print(f"{name:30s} fwd {tf:6.2f} ms   fwd+bwd {tb:6.2f} ms")
    return tf, tb


class Stem(nn.Module):
    """Stem as built by ResNetConv (StemConvS2D) or, for comparison, the
    direct 7×7/2 conv it replaced (``s2d=False`` — the BASELINE.md stem
    numbers are this pair)."""

    pool: bool = True
    s2d: bool = True

    @nn.compact
    def __call__(self, x):
        x = x.astype(jnp.bfloat16)
        if self.s2d:
            from mx_rcnn_tpu.models.backbones import StemConvS2D

            x = StemConvS2D(name="conv1")(x)
        else:
            x = nn.Conv(64, (7, 7), strides=(2, 2), padding=[(3, 3)] * 2,
                        use_bias=False, dtype=jnp.bfloat16, name="conv1")(x)
        x = nn.relu(x)
        if self.pool:
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1)] * 2)
        return x


rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(1, H, W, 3), jnp.float32)
bench_module("full r101 body (s1-4)", ResNetConv(depth="resnet101"), x)
bench_module("stem s2d (conv1+pool)", Stem(), x)
bench_module("stem direct (replaced)", Stem(s2d=False), x)
bench_module("conv1 s2d only", Stem(pool=False), x)
bench_module("conv1 direct only", Stem(pool=False, s2d=False), x)

x4 = jnp.asarray(rng.randn(1, H // 4, W // 4, 64), jnp.bfloat16)
bench_module("stage1 (3u, 256ch, /4)", ResNetStage(3, 64, 1), x4)
x8in = jnp.asarray(rng.randn(1, H // 4, W // 4, 256), jnp.bfloat16)
bench_module("stage2 (4u, 512ch, /8)", ResNetStage(4, 128, 2), x8in)
x16in = jnp.asarray(rng.randn(1, H // 8, W // 8, 512), jnp.bfloat16)
bench_module("stage3 (23u, 1024ch, /16)", ResNetStage(23, 256, 2), x16in)
