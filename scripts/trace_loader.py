#!/usr/bin/env python
"""Loader-overlap evidence (VERDICT round-3 item 2 fallback): trace the
double-buffered loader-fed train loop and report how much of the wall
window the device spent computing vs idle.

The owed number is loader-inclusive ≥ ~90% of staged; where the wall
clock is too noisy to show it, this trace is the substitute evidence —
with the round-3 ``put`` hook the host→device
transfer runs on the prefetch thread and should overlap the previous
step, so device busy-fraction ≈ staged-bench busy-fraction and any gap
is dispatch, not transfer.

  python scripts/trace_loader.py [--steps 24] [--batch 1]
"""

import argparse
import glob
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"))

import time

import jax

import bench

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=24)
ap.add_argument("--batch", type=int, default=1)
ap.add_argument("--dir", default="/tmp/prof_loader")
args = ap.parse_args()

from mx_rcnn_tpu.data.loader import AnchorLoader

state, step, _, cfg = bench.build(args.batch)
roidb = bench._synthetic_roidb()
loader = AnchorLoader(roidb, cfg, args.batch, shuffle=True, seed=0)
loader.put = jax.device_put       # transfer on the prefetch thread
for b in loader:                  # warm every bucket
    state, m = step(state, b, jax.random.PRNGKey(0))
jax.block_until_ready(m)

shutil.rmtree(args.dir, ignore_errors=True)
n = 0
t0 = time.time()
with jax.profiler.trace(args.dir):
    for i, b in enumerate(loader):
        state, m = step(state, b, jax.random.PRNGKey(i))
        n += args.batch
        if i + 1 >= args.steps:
            break
    jax.block_until_ready(m)
wall = time.time() - t0
print(f"loader-fed: {n} imgs in {wall:.3f}s = {n / wall:.2f} imgs/s wall")

from parse_xplane import main as print_xplane

pb = glob.glob(f"{args.dir}/plugins/profile/*/*.xplane.pb")[0]
print_xplane(pb, topn=25)
print("compare: device busy-sum above vs the staged bench's device step "
      "time x steps — transfer fully overlapped means equal busy-sums "
      "and the wall gap is dispatch latency only.")
