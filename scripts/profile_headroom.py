#!/usr/bin/env python
"""Resolve the backbone-conv headroom question with profiled device time.

Round-1 left a contradiction (VERDICT round 1, "What's weak" #2):
BASELINE.md said a bare stage-3 bottleneck chain reaches ~78-94 TFLOP/s
while ROADMAP called ~16 TFLOP/s the conv ceiling.  This script measures
both claims the only trustworthy way — xplane device
time ("XLA Modules" line) + XLA's own FLOP count (compiled.cost_analysis)
— for:

  * full ResNet-101 body, fwd and fwd+bwd, at the bench shape
  * stage-3 chain (23 bottleneck units) fwd and fwd+bwd
  * one bottleneck unit fwd
  * a "bare" 3x3 conv chain (the round-1 calibration shape)

and prints per-op-family time for the body fwd+bwd so conv time vs
standalone elementwise time is explicit.

Usage: python scripts/profile_headroom.py  (needs the real chip)
"""

import collections
import glob
import os
import re
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"))

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from parse_xplane import xplane_lines
from mx_rcnn_tpu.models.backbones import ResNetConv, ResNetStage, Bottleneck

assert jax.default_backend() == "tpu", jax.default_backend()

H, W = 608, 1024
REPEAT = 10


def profile(name, fn, *args, flops=None):
    """Run fn REPEAT times under a trace; return device ms/call."""
    # warm: compile + first-chain cost off the record
    for _ in range(3):
        o = fn(*args)
    jax.block_until_ready(o)
    d = f"/tmp/headroom/{name.replace(' ', '_').replace('/', '_')}"
    shutil.rmtree(d, ignore_errors=True)
    with jax.profiler.trace(d):
        for _ in range(REPEAT):
            o = fn(*args)
        jax.block_until_ready(o)
    pbs = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
    lines = xplane_lines(pbs[0])
    mods = lines.get("XLA Modules")
    if mods is None:
        print(f"{name:34s}  NO MODULE LINE ({list(lines)})")
        return None, None
    n, total = mods[0], mods[1]
    per_call = total / REPEAT
    tf = (flops / (per_call / 1e3) / 1e12) if flops else 0.0
    gf = (flops or 0) / 1e9
    print(f"{name:34s} {per_call:8.3f} ms/call   {gf:8.1f} GF   {tf:6.1f} TFLOP/s   ({n} ev)")
    return per_call, lines


def build(mod, x):
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

    fl_f = fwd.lower(params, x).compile().cost_analysis().get("flops", 0)
    fl_b = fwdbwd.lower(params, x).compile().cost_analysis().get("flops", 0)
    return params, fwd, fwdbwd, fl_f, fl_b


rng = np.random.RandomState(0)

print("=== full ResNet-101 body (s2d host layout, bench shape) ===")
x12 = jnp.asarray(rng.randn(1, H // 2, W // 2, 12), jnp.float32)
p, fwd, fwdbwd, ff, fb = build(ResNetConv(depth="resnet101"), x12)
profile("body fwd", fwd, p, x12, flops=ff)
tb, lines_b = profile("body fwd+bwd", fwdbwd, p, x12, flops=fb)

if lines_b:
    print("\n-- body fwd+bwd, per-op-family device ms (sum over "
          f"{REPEAT} calls; divide by {REPEAT}):")
    for ln in ("XLA Ops",):
        if ln in lines_b:
            for fam, ms in lines_b[ln][2].most_common(14):
                print(f"   {ms / REPEAT:8.3f} ms  {fam}")

print("\n=== stage-3 chain (23 units, 1024ch, /16) ===")
x16 = jnp.asarray(rng.randn(1, H // 8, W // 8, 512), jnp.bfloat16)
p3, fwd3, fwdbwd3, ff3, fb3 = build(ResNetStage(23, 256, 2), x16)
profile("stage3 fwd", fwd3, p3, x16, flops=ff3)
profile("stage3 fwd+bwd", fwdbwd3, p3, x16, flops=fb3)

print("\n=== one bottleneck unit (stage-3 identity shape) ===")
xu = jnp.asarray(rng.randn(1, H // 16, W // 16, 1024), jnp.bfloat16)
pu, fwdu, fwdbwdu, ffu, fbu = build(Bottleneck(256), xu)
profile("unit fwd", fwdu, pu, xu, flops=ffu)
profile("unit fwd+bwd", fwdbwdu, pu, xu, flops=fbu)


print("\n=== bare 3x3 conv chain (stage-3 spatial, 256ch) ===")


class ConvChain(nn.Module):
    n: int = 8
    f: int = 256

    @nn.compact
    def __call__(self, x):
        for i in range(self.n):
            x = nn.Conv(self.f, (3, 3), padding=[(1, 1)] * 2, use_bias=False,
                        dtype=jnp.bfloat16, name=f"c{i}")(x)
        return x


xc = jnp.asarray(rng.randn(1, H // 16, W // 16, 256), jnp.bfloat16)
pc, fwdc, fwdbwdc, ffc, fbc = build(ConvChain(), xc)
profile("bare 3x3 chain fwd", fwdc, pc, xc, flops=ffc)
profile("bare 3x3 chain fwd+bwd", fwdbwdc, pc, xc, flops=fbc)

print("\n=== matmul calibration ===")
a = jnp.asarray(rng.randn(8192, 8192), jnp.bfloat16)


@jax.jit
def mm(a):
    return a @ a


fl_mm = 2 * 8192 ** 3
profile("8k bf16 matmul", mm, a, flops=fl_mm)
