"""Device-profile the one-dispatch fori_loop train chain (bench_train_chain).

The chain wall measurement read 113.4 imgs/s classic = 8.8 ms/step where
the per-dispatch device profile reads 12.20 ms — a bench must not beat
its own device profile without an explanation.  This traces the chain(n)
program itself: the xplane module busy divided by n is the true per-step
device time inside the loop, and state.step is asserted to advance by
exactly n (no silently skipped iterations).  Divergence between in-loop
and per-dispatch step time = real program differences (loop-invariant
code motion, donation aliasing vs per-call buffer copies), not host-clock
artifacts.
"""

import glob
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"))

import jax

import bench
from parse_xplane import main as print_xplane

network = sys.argv[1] if len(sys.argv) > 1 else "resnet101"
N = 40

state, step, hbatch, cfg = bench.build(1, network, donate=False)
# bench.make_chain_fn is the ONE chain definition — this script profiles
# the exact program bench_train_chain times (a copy here once drifted is
# the bug class this script exists to catch)
chain = bench.make_chain_fn(step, jax.device_put(hbatch))


s0 = int(jax.device_get(state.step))
state = chain(state, N)  # compile + warm
s1 = int(jax.device_get(state.step))
assert s1 - s0 == N, f"chain executed {s1 - s0} steps, expected {N}"
print(f"step-count check OK: {s0} -> {s1} (+{N})")

d = "/tmp/prof_chain"
shutil.rmtree(d, ignore_errors=True)
with jax.profiler.trace(d):
    state = chain(state, N)
    _ = int(jax.device_get(state.step))

pb = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[0]
print(f"(ONE chain({N}) call, network={network}; divide busy by {N} for "
      f"per-step device ms)")
print_xplane(pb, topn=25)
