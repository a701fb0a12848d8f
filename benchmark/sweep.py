#!/usr/bin/env python3
"""The rate sweep that fixes an open-loop cell's offered rate, made once.

    python3 benchmark/sweep.py --workload c4-serve-open --seed 5 \
        --seconds 20 --rates 20 30 40 50 60 80

One process, one set-up: the server of the cell's configuration is started
once and the cell's traffic is offered at each rate in turn (a child
generator a rate).  Prints one JSON line a rate.  The knee is the highest
rate at which every request came back good, the completed rate equals the
offered one and the tails stayed in line with the rates below it (just
under the rate that sheds, every request is still answered while the
backlog grows all through the window); the cell's traffic file then fixes
0.8 of it.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from benchmark import harness
    from benchmark.drivers import serve as drv

    spec = harness.load_cell(args.workload)
    # as a run does: the sweep's server loads its programs from the cache (a
    # process that compiled them serves 8-17 % faster, PERF.md section 6)
    harness.ensure_programs_cached(spec, args.seed)
    harness.setup_compile_cache()
    harness.require_chips(spec["cell"]["chips"])
    flat = harness.modules_of(spec["config"])["weights"].make(
        spec["config"]["net"], args.seed)

    class Sweep(drv.Conductor):
        """A conductor that offers each rate in turn before the stop."""

        def _drive(self):
            base = self.spec
            for rate in args.rates:
                self.spec = copy.deepcopy(base)
                self.spec["traffic"]["rate"] = rate
                self.out = {}
                super()._drive()
                res = {k: v for k, v in self.out["result"].items()
                       if k not in ("sample", "event")}
                a = self.out["metrics_after"]["counters"]
                b = self.out["metrics_before"]["counters"]
                res.update(rate=rate, batches=a["batches"] - b["batches"],
                           served=a["served"] - b["served"])
                print(json.dumps(res), flush=True)

    drv.serve_window(spec, args.seed, args.seconds, False, flat, T_START,
                     conductor=Sweep)
    return 0


if __name__ == "__main__":
    sys.exit(main())
